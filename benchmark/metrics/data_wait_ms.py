"""Mean time a step of the window waited for its batch: ``data_ms`` of the
program's telemetry step records (host clock round ``next(it)`` in
``Trainer.run_epoch``), in milliseconds."""


def read(facts):
    rows = [r["data_ms"] for r in facts["records"] if not r.get("compile")]
    return sum(rows) / len(rows) if rows else None

"""Share of the traced window in which no operation ran on the device, in
percent: 1 - union of the device operations' intervals / traced window."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""The whole step's share of the chip's published bf16 peak, in percent:
model FLOPs of a step (forward + backward at the padded shapes, no
recomputation, ``benchmark/flops.py``) x steps of the window / its seconds /
(chips x peak).  Same window and work as ``examples_per_s_per_chip``."""


def read(facts):
    if not facts.get("peaks") or not facts["steps"]:
        return None
    achieved = facts["flops_per_step"] * facts["steps"] / facts["seconds"]
    return 100.0 * achieved / (facts["chips"]
                               * facts["peaks"]["bf16_flops_per_s"])

"""How unevenly the router loaded the held experts: the fullest held
expert's token-slots over the mean of the held ones, averaged over the
expert layers and over the steps between the loop's read-backs (the
program's ``moe_load_max`` field of the window's step records; 1 is even
load).  The grouped products' time follows the slots that landed, their
tiles the fullest expert.  None where the program writes no such field."""


def read(facts):
    loads = [r["moe_load_max"] for r in facts["records"]
             if "moe_load_max" in r]
    return sum(loads) / len(loads) if loads else None

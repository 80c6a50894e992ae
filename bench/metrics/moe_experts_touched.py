"""Distinct experts one layer reads per decode verify step, mean over the
window and drain: the program's ``stats["moe"]["decode"]`` counter of
experts touched (summed over layers and verify steps) over its steps
times ``stats["moe"]["layers"]``.  A tree of W tokens makes W x k picks
a layer; with uniform routing over E experts it touches about E (1 - (1
- k / E)^W).  Nothing to read where the program keeps no such counter."""


def read(run):
    moe = run.stats.get("moe")
    if not moe or not moe["decode"]["steps"]:
        return None
    dec = moe["decode"]
    return dec["experts_touched"] / (dec["steps"] * moe["layers"])

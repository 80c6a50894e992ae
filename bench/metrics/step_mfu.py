"""Model FLOP/s utilization of the whole step: committed output tokens in
the traced window, each at its own context, times the forward flops a
token needs (2 per weight of the target and its output head, plus
attention over the context), over the traced seconds times the chip's
bf16 peak.  Drafted and rejected tree tokens do not count."""
import numpy as np

from bench import programs, shapes


def read(run):
    if not run.trace or not run.trace["chunks"]:
        return None
    flops = 0
    for c in run.trace["chunks"]:
        ns = np.asarray(c["ns"])                    # (K, B)
        ctx = np.asarray(c["ctx0"]) + np.concatenate(
            [np.zeros((1, ns.shape[1]), ns.dtype), np.cumsum(ns, 0)[:-1]])
        for k, b in zip(*np.nonzero(ns)):
            for j in range(int(ns[k, b])):          # accepted chain tokens
                flops += shapes.token_flops(run.dims, int(ctx[k, b]) + j)
    _, window = programs.busy_and_window(run.trace)
    return 100.0 * flops / (window * run.peaks["flops_bf16"])

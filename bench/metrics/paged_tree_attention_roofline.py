"""Share of its roofline the paged tree-verify attention kernel reaches:
the least time of every call in the traced window (the larger of its
flops over the bf16 peak and its bytes over HBM bandwidth, from
``shapes.paged_tree_attention`` at each live row's context) over the
summed device time of the kernel's events.  One call per layer per decode
step: when the events found are not that many, the kernel was not found
and nothing is read."""
import numpy as np

from bench import programs, shapes


def read(run):
    if not run.trace or not run.trace["chunks"]:
        return None
    events = programs.kernel_events(programs.device(run.trace),
                                    programs.PAGED_TREE_ATTENTION)
    L = run.dims["n_layers"]
    steps = sum(c["K"] for c in run.trace["chunks"])
    if not events or len(events) != L * steps:
        return None
    W, nnz = run.trace["tree_width"], run.trace["tree_mask_nnz"]
    pk = run.peaks
    least = 0.0
    for c in run.trace["chunks"]:
        ns = np.asarray(c["ns"])
        ctx = np.asarray(c["ctx0"]) + np.concatenate(
            [np.zeros((1, ns.shape[1]), ns.dtype), np.cumsum(ns, 0)[:-1]])
        for k in range(ns.shape[0]):
            live = ctx[k][ns[k] > 0]
            f, b = shapes.paged_tree_attention(run.dims, W, nnz, live)
            least += L * max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / programs.seconds(events)

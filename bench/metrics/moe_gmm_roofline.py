"""Share of its roofline the grouped expert matmul reaches in decode: the
least time of the kernel's calls in the traced decode chunks (the larger
of flops over the bf16 peak and bytes over HBM bandwidth, per call, from
``moe_shapes.expert_matmuls``) over the summed device time of its events
inside ``jit_chunk_scan`` executions.  Picks and distinct experts per
layer-step are the means of the program's ``stats["moe"]["decode"]``
counters over the whole run.  Three calls per layer per decode step: when
the events found are not that many, the kernel was not found and nothing
is read; nothing either where the program keeps no expert counters."""
import bisect

from bench import moe_shapes, programs


def _in_decode(events, dev) -> list:
    """The events that start inside a ``jit_chunk_scan`` execution (the
    trace gives a kernel's event no program of its own; the prefill
    programs run the same kernel)."""
    spans = sorted((e[1], e[2]) for e in programs.executions(
        dev, programs.DECODE))
    starts = [s for s, _ in spans]
    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < spans[i][1]:
            out.append(e)
    return out


def read(run):
    moe = run.stats.get("moe")
    if not run.trace or not run.trace["chunks"] or not moe \
            or not moe["decode"]["steps"]:
        return None
    dev = programs.device(run.trace)
    events = _in_decode(programs.kernel_events(dev, moe_shapes.GMM), dev)
    L = run.dims["n_layers"]
    steps = sum(c["K"] for c in run.trace["chunks"])
    if not events or len(events) != L * steps * moe_shapes.CALLS_PER_LAYER:
        return None
    dec = moe["decode"]
    layer_steps = dec["steps"] * moe["layers"]
    pk = run.peaks
    least = sum(max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
                for f, b in moe_shapes.expert_matmuls(
                    run.dims, dec["picks"] / layer_steps,
                    dec["experts_touched"] / layer_steps))
    return 100.0 * L * steps * least / programs.seconds(events)

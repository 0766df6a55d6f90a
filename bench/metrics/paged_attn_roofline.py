"""Kernel ``repro.kernels.paged_decode_attention``: the least time its
useful work could take on the chip (operations over peak or bytes over
bandwidth, whichever is larger) over the kernel's own time in the trace;
the mean over the cell's chips.  Useful work counts each row's cached
tokens, not the padded block table."""
from bench import stats
from bench.work import least_time, paged_attn_bytes, paged_attn_flops



def is_kernel(op: str) -> bool:
    """The kernel's events in the trace: a Pallas call named for it, or,
    unnamed, the one Pallas call of the served step ``attend_logits``."""
    return ("paged_decode_attention" in op
            or (op.startswith("%attend_logits") and "tpu_custom_call" in op))


def read(run):
    if run.peak is None:
        return None
    shares = []
    for idx, tr in run.traces.items():
        work = stats.window_work(run, idx)
        t_min, _ = least_time(paged_attn_flops(run.widths, work),
                              paged_attn_bytes(run.widths, work), run.peak)
        for dev in tr.values():
            ns = sum(v for k, v in dev["op_ns"].items() if is_kernel(k))
            if ns and t_min:
                shares.append(100.0 * t_min / (ns / 1e9))
    return sum(shares) / len(shares) if shares else None

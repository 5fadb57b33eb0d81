"""What the served tokens of a traced window cost, counted from shapes.

Shared by the readers of `step_mfu.serve`, `decode_tick_roofline` and
`paged_attn_roofline`: token j of a request with an n-token prompt was
delivered by the prefill of the prompt (j = 0) or by a decode step whose
input attended n + j positions.
"""

from __future__ import annotations


def served_flops(ctx, lo: float, hi: float) -> float:
    """Forward FLOPs of every token delivered in [lo, hi) on the host's
    clock; a prompt counts when its first token is delivered."""
    counts = ctx.family
    total = 0.0
    for r in ctx.records:
        for j, t in enumerate(r.token_times):
            if not lo <= t < hi:
                continue
            if j == 0:
                total += counts.prefill_flops(ctx.config, r.prompt_len)
            else:
                total += counts.forward_flops_token(
                    ctx.config, r.prompt_len + j, head=True)
    return total


def decode_work(ctx):
    """(seconds, bytes, FLOPs, KV bytes) of the traced window's decode
    ticks, or None where the trace holds none."""
    runs = ctx.trace.program_runs(ctx.mix["programs"]["tick"])
    if not runs or ctx.trace_span is None:
        return None
    lo, hi = ctx.trace_span
    counts = ctx.family
    kv = counts.kv_bytes_per_position(ctx.config)
    kv_bytes = flops = 0.0
    for r in ctx.records:
        for j, t in enumerate(r.token_times):
            if j >= 1 and lo <= t < hi:
                kv_bytes += kv * (r.prompt_len + j)
                flops += counts.forward_flops_token(
                    ctx.config, r.prompt_len + j, head=True)
    if flops == 0:
        return None
    seconds = sum(r.dur for r in runs) / 1e9
    weights = len(runs) * counts.decode_weight_bytes(ctx.config)
    return seconds, weights + kv_bytes, flops, kv_bytes

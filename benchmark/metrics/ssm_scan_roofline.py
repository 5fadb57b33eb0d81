"""The selective-scan kernel of a model with state-space layers against
its memory roofline: what the `ssm_scan` kernel of the traced prefill
chunks has to read and write (`family.scan_bytes`: `delta`, `u = delta
h`, `B` and `C` read, `y` written, the state in and out, for a chunk's
positions in every mamba layer; a chunk's call scans all its positions,
the padded tail of a prompt's last chunk included, so the count takes the
mix's `prefill_chunk` a call) over peak bandwidth, over the kernel's
device time inside the chunk program. The kernel is bound by its `exp`
and multiply-adds on the VPU, not by its bytes: the share reads well
under 100, and the gap is what a faster scan would close."""


def read(ctx):
    fam = ctx.family
    runs = ctx.trace.program_runs(ctx.mix["programs"]["chunk"])
    if not hasattr(fam, "scan_bytes") or not runs:
        return None
    seconds = ctx.trace.scope_time(ctx.mix.get("scan_scope", "ssm_scan"),
                                   runs)
    if not seconds:
        return None
    nbytes = len(runs) * fam.scan_bytes(ctx.config,
                                        ctx.mix["engine"]["prefill_chunk"])
    return 100.0 * nbytes / ctx.peaks.hbm_bytes_per_s / seconds

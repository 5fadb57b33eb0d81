"""Compiled-artifact invariants: what a train step's executable looks like.

The regression tripwires a round without chip time still has (rounds
3-4 had none): instead of a throughput number, assert properties of the
COMPILED program that predict throughput — per-device flops and peak
temp memory from XLA's own analyses, and the collective-op census of
the optimized (post-SPMD-partitioning) HLO. Any
change that bloats memory, adds a collective, or changes the op mix fails
against committed numbers in tests/test_compiled_invariants.py on the CPU
sim, no hardware needed. This generalizes the round-4 one-off of
byte-diffing lowered HLO between commits (BASELINE.md "Pallas kernel
unification") into a harness of committed numbers. Reference analog:
the benchmark-as-test harness at 03_model_parallel.ipynb:403-423 — this
is its works-without-a-chip half.
"""

from __future__ import annotations

import re

# The full XLA collective vocabulary a step can emit. Async pairs
# (`all-reduce-start`/`-done`) count once, as the -start; `-done` and
# fused variants with extra suffixes are excluded by requiring `(` right
# after the op name.
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "ragged-all-to-all",
    "collective-broadcast",
)


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Census of collective ops in an HLO module's text, keyed by op name.

    Run it on OPTIMIZED HLO (`compiled.as_text()`): collectives are
    inserted by the SPMD partitioner during compilation, so pre-optimized
    (`lowered.as_text()`) modules show shardings but few/no collectives.
    Zero-count ops are included so equality against a committed dict also
    catches a collective *appearing* where none was."""
    return {
        op: len(re.findall(rf"{op}(?:-start)?\(", hlo_text))
        for op in COLLECTIVE_OPS
    }


# Bytes per element of the HLO shape dtypes a collective can carry.
# Sub-byte types (s4/u4) round up to 1 — they only appear packed in
# exotic programs and a 2x overestimate beats a KeyError census hole.
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_ARRAY_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")

# A collective's defining line: `%name = <shape> <op>(...)` where <shape>
# is an array (`f32[16,8]{1,0}`), a flat tuple (`(f32[8]{0}, f32[8]{0})`),
# or — for variadic async starts — a tuple nesting one level of tuples
# (`((f32[a], f32[b]), (f32[a], f32[b]))`). `-start` counts (the async op
# carries the transfer); `-done` does not (no `(` follows the op stem).
# Longest-first alternation so ragged all-to-alls are not double-counted
# as plain ones.
_COLL_DEF_RE = re.compile(
    r"=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+("
    + "|".join(sorted(COLLECTIVE_OPS, key=len, reverse=True))
    + r")(-start)?\(")

# -start ops whose staging tuple follows the (operand(s), result(s),
# context...) convention — only element [1] is the transferred data.
# all-reduce-start is NOT here: its tuple (when variadic) IS the result
# set, so every element counts.
_START_OPERAND_RESULT = ("all-gather", "collective-permute", "all-to-all",
                         "ragged-all-to-all", "collective-broadcast",
                         "reduce-scatter")


def _split_top_level(tuple_str: str) -> list[str]:
    """Top-level elements of a (possibly nested) HLO tuple string:
    "(f32[4,8]{1,0}, (b, c))" → ["f32[4,8]{1,0}", "(b, c)"] — commas
    inside nested tuples, dim brackets, and layout braces don't split."""
    parts, depth, cur = [], 0, []
    for ch in tuple_str[1:-1]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts]


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _ARRAY_SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            if dtype.startswith("f8"):  # f8e4m3fn and friends
                size = 1
            else:
                continue  # token/opaque pseudo-shapes carry no data
        else:
            size = _DTYPE_BYTES[dtype]
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device result bytes moved by each collective op kind, from the
    operand/result shapes in OPTIMIZED HLO text — the comm-volume half of
    the census `collective_counts` only counts.

    The number is the op's *result-shape* footprint summed over its
    occurrences: for all-reduce that equals the reduced tensor, for
    all-gather the full gathered output, for reduce-scatter the local
    shard. It is a per-step, per-device accounting quantity (what
    `StepAccounting` reports as comm-bytes/step), not a link-level
    traffic model — algorithm factors (ring all-reduce moves ~2x the
    tensor over the wire) are deliberately not applied. Async pairs
    count once at the `-start`, per-op tuple semantics: for the
    (operand(s), result(s), context...) ops (_START_OPERAND_RESULT) only
    top-level element [1] — which may itself be a variadic tuple — is
    the transferred data, so neither the in-flight operand copies nor
    TPU context tokens (trailing `u32[]` scalars on e.g.
    collective-permute-start) are billed; all-reduce-start's tuple IS
    its (variadic) result set and counts whole."""
    out = {op: 0 for op in COLLECTIVE_OPS}
    for m in _COLL_DEF_RE.finditer(hlo_text):
        shape_str, op, is_start = m.group(1), m.group(2), m.group(3)
        if is_start and shape_str.startswith("("):
            parts = _split_top_level(shape_str)
            # scalar u32/s32 trailers are async context tokens, not data
            parts = [p for p in parts
                     if not re.match(r"[su]32\[\]", p)]
            if op in _START_OPERAND_RESULT and len(parts) >= 2:
                parts = [parts[1]]
            out[op] += sum(_shape_bytes(p) for p in parts)
        else:
            out[op] += _shape_bytes(shape_str)
    return out


# one async pair: `%name = ... <op>-start(...)` later consumed by
# `<op>-done(...%name...)`. Matched by value name within the module text —
# HLO instruction names are unique per computation and the pair never
# crosses one. The type between `=` and the op is usually a TUPLE with
# internal spaces (`(f32[8]{0}, f32[8]{0}) all-gather-start(...)` — the
# staging tuple every async start returns), so the shape alternation
# mirrors _COLL_DEF_RE's rather than assuming one token.
_ASYNC_START_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*(?:\((?:[^()]|\([^()]*\))*\)|\S+)\s+("
    + "|".join(sorted(COLLECTIVE_OPS, key=len, reverse=True))
    + r")-start\(")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s")


def overlap_census(hlo_text: str) -> dict:
    """Census of the latency-hiding structure of an optimized HLO module
    (ISSUE 5c) — the compile-time evidence for the overlap the scheduler
    flags (trainer._TPU_OVERLAP_COMPILER_OPTIONS) and the ring matmuls
    (ops/overlap.py) claim:

      * ``async_pairs`` — per collective kind, how many ``-start`` ops
        have a matching ``-done`` (on TPU with the latency-hiding
        scheduler every collective should pair; XLA:CPU lowers most
        collectives synchronously, so sim programs legitimately show 0);
      * ``unpaired_starts`` — starts with no done: must be 0 in any
        well-formed module, a nonzero value means the census regexes
        (or the compiler) broke;
      * ``overlapped_ops`` — instructions scheduled strictly BETWEEN a
        start and its done, summed over pairs: the work the scheduler
        actually placed inside collective windows. Post-scheduling HLO
        text is in execution order, so text distance is schedule
        distance; 0 with nonzero pairs means the async pair is
        vestigial (nothing hidden);
      * ``ppermute`` — collective-permute count (async starts count
        once): the chunked collective-matmul signature. Each ring
        contributes exactly (ring_size - 1) hops per traveling operand,
        which is what tests/test_overlap.py pins against the tp size.
    """
    starts: dict[str, tuple[str, int]] = {}
    pairs = {op: 0 for op in COLLECTIVE_OPS}
    overlapped = 0
    instr_idx = 0
    for line in hlo_text.splitlines():
        is_instr = bool(_INSTR_RE.match(line))
        if is_instr:
            instr_idx += 1
        m = _ASYNC_START_RE.search(line)
        if m:
            starts[m.group(1)] = (m.group(2), instr_idx)
            continue
        done = re.search(r"[\w\-]+-done\(", line)
        if done:
            # the done's single operand is the start value; real dumps
            # print it behind its (possibly tuple) shape and with or
            # without the legacy '%' sigil (`all-gather-done((f32[8],
            # f32[16]) %ag.1)`), so rather than parse shape grammar,
            # take the first token that names a recorded start — shape
            # tokens (`f32`, dims) can never collide with instruction
            # names like `all-gather-start.1`
            for tok in re.findall(r"[\w.\-]+", line[done.end():]):
                if tok in starts:
                    op, start_idx = starts.pop(tok)
                    pairs[op] += 1
                    overlapped += max(0, instr_idx - start_idx - 1)
                    break
    return {
        "async_pairs": pairs,
        "unpaired_starts": len(starts),
        "overlapped_ops": overlapped,
        "ppermute": len(re.findall(
            r"collective-permute(?:-start)?\(", hlo_text)),
    }


def a2a_census(hlo_text: str) -> dict[str, int]:
    """The expert-parallel dispatch/combine signature (ISSUE 14): total
    ``all-to-all`` occurrences (plain + ragged, async starts counted
    once) and their per-device result bytes. The MoE a2a path
    (ops/overlap.expert_a2a_ffn) emits exactly 2 per MoE layer forward
    (dispatch + combine) and 2 more in backward — ×chunks when capacity
    pipelining splits them — so the committed ``count`` pins both that
    the explicit exchange actually lowered to all_to_all (not the
    partitioner's allgather+dynamic-slice fallback) and that no pass
    duplicated it; ``bytes`` pins the payload (int8 dispatch payloads
    shrink it ~4x minus the fp32 scale sidecar)."""
    counts = collective_counts(hlo_text)
    nbytes = collective_bytes(hlo_text)
    kinds = ("all-to-all", "ragged-all-to-all")
    return {"count": sum(counts[k] for k in kinds),
            "bytes": sum(nbytes[k] for k in kinds)}


def int8_counts(hlo_text: str) -> dict[str, int]:
    """Census of the int8 quantized-matmul op mix (ops/quant.py):
    ``s8_values`` — instructions producing an s8 tensor (the per-operand
    quantize converts; fusion bodies included, the text covers them);
    ``int_dots`` — dot instructions with s32 (int-accumulated) output.
    Both zero in an unquantized program, which is itself a tripwire: an
    int8 op appearing in a bf16 config's step is never an accident."""
    return {
        "s8_values": len(re.findall(r"= s8\[", hlo_text)),
        "int_dots": len(re.findall(r"= s32\[[^\]]*\]\S* dot\(", hlo_text)),
    }


def hlo_fingerprint(compiled) -> str:
    """sha256 of the executable's optimized-HLO text — the byte-identity
    tripwire (ISSUE 6): two compiles whose fingerprints match ran the
    same program, to the byte. Used to prove the diagnostics knob's OFF
    path adds literally nothing to a train step (the committed numeric
    invariants bound drift; this bounds it to zero). What is hashed is
    the program, not where it was traced from: the text also carries a
    file/line/column table and a per-op ``metadata={... stack_frame_id}``
    that differ between two call sites of one program."""
    import hashlib

    text = re.sub(r"(?ms)^FileNames\n.*?^StackFrames\n(?:\d+ \{[^\n]*\n)*",
                  "", compiled.as_text())
    text = re.sub(r",? ?metadata=\{[^{}]*\}", "", text)
    return hashlib.sha256(text.encode()).hexdigest()


def compiled_invariants(compiled) -> dict:
    """The committed-invariant dict for one compiled train step.

    * ``flops`` — XLA cost analysis, per device (post-partitioning).
    * ``temp_bytes`` — peak scratch memory of the executable: the
      activation / workspace footprint buffer assignment settled on.
    * ``arg_bytes`` — total input size: params + optimizer state + batch.
      The cheapest state-bloat tripwire there is (round 3's regression —
      BN buffers riding the optimizer tree — was exactly an arg_bytes
      growth).
    * ``alias_bytes`` — input bytes aliased to outputs: the DONATION
      tripwire. The train step donates its TrainState; if a jit change
      silently breaks donation (a dtype/sharding mismatch between the
      donated input and the output is enough — jax only warns), the step
      holds two copies of params+opt state and a model sized near HBM
      OOMs. alias ≈ state bytes is the proof donation still holds.
    * ``collectives`` — `collective_counts` of the optimized HLO.
    * ``int8_ops`` — `int8_counts`: the quantized-matmul convert/dot mix
      (all-zero for unquantized configs).
    * ``comm_bytes`` — `collective_bytes`: per-device result bytes by
      collective kind. Together with ``flops`` these are the
      StepAccounting inputs (telemetry/accounting.py), so committing
      them makes MFU / comm-volume math a CI tripwire: a partitioning
      change that moves communication volume — or an accounting bug
      that would misreport MFU — fails against the pinned numbers.
    * ``overlap`` — `overlap_census`: async start/done pairing, ops
      scheduled inside collective windows, and the ppermute ring count
      (the chunked collective-matmul signature — ISSUE 5).
    * ``a2a`` — `a2a_census`: all-to-all count + bytes, the
      expert-parallel MoE dispatch/combine signature (ISSUE 14).
    """
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax wraps it in a list
        cost = cost[0] if cost else {}
    text = compiled.as_text()
    return {
        "flops": float(cost.get("flops", -1.0)),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "arg_bytes": int(mem.argument_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "collectives": collective_counts(text),
        "int8_ops": int8_counts(text),
        "comm_bytes": collective_bytes(text),
        "overlap": overlap_census(text),
        "a2a": a2a_census(text),
    }

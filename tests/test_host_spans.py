"""The one host-span instrument (ISSUE 27): `telemetry/spans.py`'s ring
(parent, ids, snapshot, the profiler annotation, `host/gc`), the spans
the router, the engine and the Trainer enter with NO telemetry directory,
and the counters at the same boundaries (queue wait + prefill span ==
TTFT). All on the CPU sim; nothing here is a timing."""

import collections
import functools
import gc
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.serving import ReplicaRouter, ServingEngine
from pytorchdistributed_tpu.telemetry import spans
from pytorchdistributed_tpu.telemetry.spans import SpanTracer
from pytorchdistributed_tpu.telemetry.tracing import (
    critical_paths,
    read_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = gpt2_config("test", num_layers=2, max_seq_len=64)
# same geometry as tests/test_tracing.py
ENGINE_KW = dict(num_slots=3, prefill_bucket=16, block_size=8)


@functools.cache
def _setup():
    model = GPT2(CFG)
    return model, model.init(jax.random.key(1),
                             jnp.zeros((1, 4), jnp.int32))


def _prompts(*lens):
    rng = np.random.default_rng(7)
    return [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
            for m in lens]


@pytest.fixture
def no_files(tmp_path, monkeypatch):
    """A run with no telemetry directory, in an empty working directory
    that must still be empty afterwards."""
    monkeypatch.delenv("PTD_TELEMETRY_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    spans.ring().clear()
    yield tmp_path
    assert list(tmp_path.iterdir()) == [], "a run without a directory " \
        "wrote files"


def _parents(snap):
    """{span name: set of its parents' names} over a snapshot."""
    by_id = {s.id: s for s in snap}
    out: dict = {}
    for s in snap:
        parent = by_id.get(s.parent)
        out.setdefault(s.name, set()).add(
            parent.name if parent is not None else None)
    return out


# ----------------------------------------------------------------------
# the ring


def test_parent_and_ids_in_ring_and_chrome_trace(tmp_path):
    tr = SpanTracer(rank=2)
    with tr.span("serve/engine_step", step=7):
        with tr.span("serve/admit"):
            with tr.span("serve/prefill", request=3, pos=16):
                pass
        with tr.span("serve/deliver") as d:
            d.note(tokens=5)
    snap = tr.snapshot()
    # children end (and land) before their parents
    assert [s.name for s in snap] == [
        "serve/prefill", "serve/admit", "serve/deliver",
        "serve/engine_step"]
    pre, adm, dlv, step = snap
    assert step.parent is None and step.ids == {"step": 7}
    assert adm.parent == step.id and dlv.parent == step.id
    assert pre.parent == adm.id and pre.ids == {"request": 3, "pos": 16}
    assert dlv.ids == {"tokens": 5}
    assert len({s.id for s in snap}) == 4
    assert all(s.t0_ns <= s.t1_ns for s in snap)
    assert step.t0_ns <= adm.t0_ns and adm.t1_ns <= step.t1_ns
    # a second thread of spans starts at the top again
    with tr.span("serve/submit", request=4):
        pass
    assert tr.snapshot()[-1].parent is None
    assert tr.totals()["serve/admit"][1] == 1

    tr.dump(tmp_path / "spans_rank2.trace.json")
    xs = [e for e in json.loads(
        (tmp_path / "spans_rank2.trace.json").read_text())["traceEvents"]
        if e["ph"] == "X"]
    by_name = {e["name"]: e for e in xs}
    assert by_name["serve/prefill"]["args"] == {
        "id": pre.id, "parent": adm.id, "request": 3, "pos": 16}
    assert by_name["serve/engine_step"]["args"]["parent"] is None
    assert all(e["pid"] == 2 for e in xs)


def test_dump_keeps_own_replica_and_unowned_spans():
    """In-process replicas share the ring: a dump for replica 1 holds the
    spans under its `replica` id (directly or through a parent) and the
    spans that carry none."""
    tr = SpanTracer(rank=0)
    with tr.span("serve/router_step", step=1):
        for rep in (0, 1):
            with tr.span("serve/replica_step", replica=rep):
                with tr.span("serve/engine_step", step=1):
                    with tr.span(f"only/{rep}"):
                        pass
    xs = [e for e in tr.to_chrome_trace(rank=1, replica=1)["traceEvents"]
          if e["ph"] == "X"]
    names = [e["name"] for e in xs]
    assert names.count("serve/engine_step") == 1
    assert "only/1" in names and "only/0" not in names
    assert "serve/router_step" in names
    assert all(e["pid"] == 1 for e in xs)
    every = tr.to_chrome_trace()["traceEvents"]
    assert sum(e["ph"] == "X" for e in every) == 7


def test_snapshot_returns_the_spans_that_ended_in_the_window():
    import time

    tr = SpanTracer(rank=0)
    with tr.span("before"):
        pass
    t0 = time.perf_counter()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        t_mid = time.perf_counter()
    t1 = time.perf_counter()
    with tr.span("after"):
        pass
    assert [s.name for s in tr.snapshot(t0, t1)] == ["inner", "outer"]
    # a span counts where it ENDED
    assert [s.name for s in tr.snapshot(t0, t_mid)] == ["inner"]
    assert [s.name for s in tr.snapshot(t1)] == ["after"]
    assert len(tr.snapshot()) == 4
    assert tr.snapshot(t1 + 60) == []
    # a dump can leave out what ended before its owner existed
    names = [e["name"] for e in tr.to_chrome_trace(since=t1)["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["after"]


def test_span_enters_a_trace_annotation_of_the_bare_name(monkeypatch):
    seen = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            assert not kw, "ids must stay out of the profiler's event"
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(spans, "_annotation", FakeAnnotation)
    tr = SpanTracer(rank=0)
    with tr.span("serve/prefill", request=12, pos=128):
        with tr.span("serve/prefill_sync", request=12):
            pass
    assert seen == [("enter", "serve/prefill"),
                    ("enter", "serve/prefill_sync"),
                    ("exit", "serve/prefill_sync"),
                    ("exit", "serve/prefill")]


def test_spans_module_imports_and_builds_a_ring_without_jax():
    """The launcher builds tracers before any backend: `spans.py` alone
    must import, build a ring and dump it with `jax` unimportable; only
    ENTERING a span reaches for `jax.profiler`."""
    code = (
        "import importlib.util, sys\n"
        "sys.modules['jax'] = None\n"   # any `import jax` now raises
        "spec = importlib.util.spec_from_file_location('spans', "
        "'pytorchdistributed_tpu/telemetry/spans.py')\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "r = m.ring()\n"
        "assert m.ring() is r and len(r) == 0\n"
        "assert r.to_chrome_trace()['traceEvents']\n"
        "try:\n"
        "    with m.span('x'):\n"
        "        pass\n"
        "except ImportError:\n"
        "    print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_generation_two_collection_is_a_host_gc_span():
    ring = spans.ring()
    assert spans.ring() is ring
    ring.clear()
    with spans.span("train/step", step=1) as outer:
        gc.collect()          # generation 2
        gc.collect(0)         # a young collection is not recorded
    snap = ring.snapshot()
    assert [s.name for s in snap] == [spans.GC_SPAN, "train/step"]
    assert snap[0].parent == snap[1].id == outer._id


# ----------------------------------------------------------------------
# the engine: spans with no directory, counters at the same boundaries


def _engine(paged: bool) -> ServingEngine:
    model, params = _setup()
    kw = ENGINE_KW if paged else dict(num_slots=3, prefill_bucket=16)
    return ServingEngine(model, params, **kw)


def test_engine_without_directory_fills_the_ring(no_files):
    engine = _engine(paged=True)
    assert engine.telemetry is None
    for p in _prompts(20, 5, 9):
        engine.submit(p, max_new_tokens=4)
    engine.run_until_idle()
    snap = spans.snapshot()
    got = _parents(snap)
    want = {
        "serve/engine_step": {None},
        "serve/admit": {"serve/engine_step"},
        "serve/start_prefill": {"serve/admit"},
        "serve/prefill": {"serve/admit"},
        "serve/chunk_operands": {"serve/prefill"},
        "serve/chunk_call": {"serve/prefill"},
        "serve/prefill_sync": {"serve/prefill"},
        "serve/grow_slots": {"serve/engine_step"},
        "serve/decode_tick": {"serve/engine_step"},
        "serve/tick_dispatch": {"serve/decode_tick"},
        "serve/tick_operands": {"serve/tick_dispatch"},
        "serve/tick_call": {"serve/tick_dispatch"},
        "serve/tick_sync": {"serve/decode_tick"},
        "serve/deliver": {"serve/engine_step"},
    }
    assert {k: got.get(k) for k in want} == want
    by_name: dict = {}
    for s in snap:
        by_name.setdefault(s.name, []).append(s)
    steps = [s.ids["step"] for s in by_name["serve/engine_step"]]
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    # the 20-token prompt is two chunks of 16 at positions 0 and 16
    chunks = [(s.ids["request"], s.ids["pos"])
              for s in by_name["serve/prefill"]]
    first = chunks[0][0]
    assert chunks[:2] == [(first, 0), (first, 16)]
    assert len(by_name["serve/start_prefill"]) == 3
    assert len(by_name["serve/prefill_sync"]) == 3
    assert all(1 <= s.ids["tokens"] <= 3 for s in by_name["serve/deliver"])
    # one tick = one dispatch + one sync
    assert (len(by_name["serve/decode_tick"])
            == len(by_name["serve/tick_dispatch"])
            == len(by_name["serve/tick_sync"]))
    engine.close()


def _children(snap):
    """{span id: [its children's names, in the order they were entered]}
    over a snapshot."""
    out: dict = {}
    for s in sorted(snap, key=lambda s: s.t0_ns):
        out.setdefault(s.parent, []).append(s.name)
    return out


def _under(snap, top):
    """The spans of a snapshot under the span `top`, itself included."""
    by_id = {s.id: s for s in snap}

    def inside(s):
        while s is not None and s.id != top.id:
            s = by_id.get(s.parent)
        return s is not None

    return [s for s in snap if inside(s)]


@pytest.mark.parametrize("spec_k", [0, 4], ids=["plain", "spec"])
def test_tick_dispatch_is_its_operands_and_its_call(spec_k, no_files):
    """`serve/tick_dispatch` is the tick's puts (`serve/tick_operands`)
    and the call of the jitted program (`serve/tick_call`), one of each a
    tick and nothing else, in the plain tick and in the speculative one;
    what the two leave uncovered is their own entries and exits."""
    model, params = _setup()
    engine = ServingEngine(model, params, spec_k=spec_k, **ENGINE_KW)
    for p in _prompts(20, 5, 9):
        engine.submit(p, max_new_tokens=16)
    engine.run_until_idle()
    snap = spans.snapshot()
    kids = _children(snap)
    by_id = {s.id: s for s in snap}
    dispatches = [s for s in snap if s.name == "serve/tick_dispatch"]
    assert len(dispatches) == engine.summary()["ticks"] > 3
    tick = "serve/spec_tick" if spec_k else "serve/decode_tick"
    uncovered = []
    for d in dispatches:
        assert by_id[d.parent].name == tick
        assert kids[d.id] == ["serve/tick_operands", "serve/tick_call"]
        inner = [s for s in snap if s.parent == d.id]
        assert all(d.t0_ns <= s.t0_ns <= s.t1_ns <= d.t1_ns for s in inner)
        # both are leaves (a collection may fall into the first call,
        # which compiles)
        assert all(set(kids.get(s.id, ())) <= {spans.GC_SPAN}
                   for s in inner)
        uncovered.append(d.t1_ns - d.t0_ns
                         - sum(s.t1_ns - s.t0_ns for s in inner))
    # two entries and two exits at tests/test_telemetry.py's 10 us a
    # span, with room: the best tick, so a core shared with five other
    # workers cannot fail it
    assert 0 <= min(uncovered) < 30_000, uncovered
    engine.close()


def test_a_chunk_is_its_operands_its_call_and_on_the_last_its_sync(
        no_files):
    engine = _engine(paged=True)
    reqs = [engine.submit(p, max_new_tokens=3) for p in _prompts(40, 5)]
    engine.run_until_idle()
    snap = spans.snapshot()
    kids = _children(snap)
    chunks = [s for s in snap if s.name == "serve/prefill"]
    # the 40-token prompt is three chunks of 16, the 5-token one is one
    assert [(s.ids["request"], s.ids["pos"]) for s in chunks] == [
        (reqs[0].id, 0), (reqs[0].id, 16), (reqs[0].id, 32),
        (reqs[1].id, 0)]
    body = ["serve/chunk_operands", "serve/chunk_call"]
    assert [kids[s.id] for s in chunks] == [
        body, body, body + ["serve/prefill_sync"],
        body + ["serve/prefill_sync"]]
    for s in snap:
        if s.name in body:
            assert set(kids.get(s.id, ())) <= {spans.GC_SPAN}, s.name
    engine.close()


def test_a_steps_own_spans_say_what_it_carried(no_files):
    """What `benchmark/stepread.py` sorts steps by, with no id for it: a
    step's `serve/prefill` spans are the chunks it ran (`summary()`'s
    delta), its `serve/prefill_sync` spans the admissions it completed,
    and its tick's `serve/deliver` carries the streams it handed a token
    (what `step()` returned)."""
    engine = _engine(paged=True)
    prompts = _prompts(5, 40, 9, 7)        # 4 requests, 3 slots
    engine.submit(prompts[0], max_new_tokens=8)
    returned, chunk_counts = [engine.step()], [1]
    # with a stream live, a step spends one chunk of the 40-token prompt
    for p in prompts[1:]:
        engine.submit(p, max_new_tokens=5)
    while engine.queue_depth or engine.prefilling_count \
            or engine.active_count:
        before = engine.summary()["prefill_chunks"]
        returned.append(engine.step())
        chunk_counts.append(engine.summary()["prefill_chunks"] - before)
    snap = spans.snapshot()
    steps = [s for s in snap if s.name == "serve/engine_step"]
    assert len(steps) == len(returned)
    carried = []
    for top, got, chunks in zip(steps, returned, chunk_counts):
        assert set(top.ids) == {"step"}
        inside = _under(snap, top)
        count = collections.Counter(s.name for s in inside)
        assert count["serve/prefill"] == chunks
        assert count["serve/prefill_sync"] == got["admitted"]
        # a plain tick hands every live slot one token
        (deliver,) = [s for s in inside if s.name == "serve/deliver"]
        assert deliver.ids == {"tokens": got["decoded"]}
        carried.append((chunks, got["admitted"]))
    summary = engine.summary()
    assert sum(c for c, _ in carried) == summary["prefill_chunks"]
    assert sum(a for _, a in carried) == summary["prefills"] == 4
    # a step that ran a chunk, a step that ran none; a chunk that
    # completed no admission
    assert {0, 1} <= {min(c, 1) for c, _ in carried}
    assert any(c and not a for c, a in carried)
    engine.close()


# A step's spans, counted, so that one more shows in a diff. PR 38 added
# at most six to an engine step: two a tick (`serve/tick_operands`,
# `serve/tick_call`), two a chunk (`serve/chunk_operands`,
# `serve/chunk_call`) and, behind a router on a probing step,
# `serve/probe` > `serve/probe_sync`. A span is ~3 us
# (tests/test_telemetry.py pins 10).
TICK = ["serve/engine_step", "serve/admit", "serve/grow_slots",
        "serve/decode_tick", "serve/tick_dispatch", "serve/tick_operands",
        "serve/tick_call", "serve/tick_sync", "serve/deliver"]
CHUNK = ["serve/prefill", "serve/chunk_operands", "serve/chunk_call"]
ADMISSION = ["serve/start_prefill"] + CHUNK + ["serve/prefill_sync"]
ROUTER = ["serve/router_step", "serve/router_health",
          "serve/router_dispatch", "serve/replica_step",
          "serve/router_reap"]
PROBE = ["serve/probe", "serve/probe_sync"]


def _one_step_each(snap, top_name):
    """[sorted names of the spans under one step] for every step."""
    return [sorted(s.name for s in _under(snap, top))
            for top in snap if top.name == top_name]


def test_spans_a_step_of_the_toy_engine_enters(no_files):
    engine = _engine(paged=True)
    short, long = _prompts(5, 20)
    # a step that starts an admission, finishes it in one chunk and ticks
    engine.submit(short, max_new_tokens=8)
    engine.step()
    (first,) = _one_step_each(spans.snapshot(), "serve/engine_step")
    assert first == sorted(TICK + ADMISSION) and len(first) == 14
    # behind a live stream a step spends one chunk: the 20-token prompt's
    # first, then its last, which completes the admission; then ticks
    spans.ring().clear()
    engine.submit(long, max_new_tokens=2)
    engine.run_until_idle()
    steps = _one_step_each(spans.snapshot(), "serve/engine_step")
    assert steps[0] == sorted(TICK + ["serve/start_prefill"] + CHUNK)
    assert steps[1] == sorted(TICK + CHUNK + ["serve/prefill_sync"])
    assert len(steps[0]) == len(steps[1]) == 13
    assert steps[2:] and all(s == sorted(TICK) for s in steps[2:])
    assert len(TICK) == 9
    engine.close()


def test_spans_a_step_behind_a_router_enters(no_files):
    """`serve/probe` > `serve/probe_sync` lies under `serve/router_health`
    on the steps that probe, every fourth, and on no other: a reader
    counts a step's probes by the `serve/probe` spans under it."""
    model, params = _setup()
    router = ReplicaRouter(model, params, replicas=1, health_every=4,
                           engine_kwargs=ENGINE_KW, warmup_lens=(16,))
    router.warmup()
    spans.ring().clear()
    router.submit(_prompts(5)[0], max_new_tokens=12)
    router.run_until_idle()
    snap = spans.snapshot()
    by_id = {s.id: s for s in snap}
    steps = [s for s in snap if s.name == "serve/router_step"]
    assert len(steps) >= 8
    probing = [s for s in steps if s.ids["step"] % 4 == 0]
    assert len(probing) >= 2
    for top in steps:
        names = sorted(s.name for s in _under(snap, top))
        probed = top in probing
        assert set(top.ids) == {"step"}
        assert names.count("serve/probe") == int(probed)
        if top.ids["step"] == steps[0].ids["step"]:
            continue     # the step that placed and admitted the request
        engine_part = TICK if "serve/engine_step" in names else []
        assert names == sorted(ROUTER + engine_part
                               + (PROBE if probed else [])), top.ids
    assert any(len(_under(snap, top)) == 16 for top in probing)
    for s in snap:
        if s.name == "serve/probe":
            health = by_id[s.parent]
            assert health.name == "serve/router_health"
            assert by_id[health.parent] in probing
        elif s.name == "serve/probe_sync":
            assert by_id[s.parent].name == "serve/probe"
    assert (sum(s.name == "serve/probe" for s in snap)
            == sum(s.name == "serve/probe_sync" for s in snap)
            == len(probing))
    router.close()


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_queue_wait_plus_prefill_span_is_ttft(paged, no_files):
    engine = _engine(paged)
    reqs = [engine.submit(p, max_new_tokens=6)
            for p in _prompts(40, 6, 11, 7, 5)]   # 5 requests, 3 slots
    engine.step()
    if paged:
        # a preempted request that is admitted again keeps its stamp and
        # adds no second entry
        slot = max(engine._active)
        victim = engine._active[slot]
        stamp = victim.admit_time
        assert stamp is not None
        engine._preempt(slot)
    engine.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    if paged:
        assert victim.admit_time == stamp and victim.preemptions == 1
    st = engine._stats
    assert (len(st["ttft_s"]) == len(st["queue_wait_s"])
            == len(st["prefill_span_s"]) == len(reqs))
    for ttft, wait, pre in zip(st["ttft_s"], st["queue_wait_s"],
                               st["prefill_span_s"]):
        assert wait >= 0 and pre > 0
        assert wait + pre == pytest.approx(ttft, abs=1e-9)
    for r in reqs:
        assert r.submit_time <= r.admit_time <= r.first_token_time
        assert ((r.admit_time - r.submit_time)
                + (r.first_token_time - r.admit_time)
                == pytest.approx(r.ttft_s, abs=1e-9))
    # the requests behind the three slots waited for one
    assert max(st["queue_wait_s"]) > min(st["queue_wait_s"])
    s = engine.summary()
    assert s["queue_wait_ms_p50"] <= s["queue_wait_ms_p95"]
    assert s["prefill_span_ms_p50"] > 0
    assert s["admit_blocked"] == 0
    engine.reset_stats()
    assert engine._stats["queue_wait_s"] == []
    assert "queue_wait_ms_p50" not in engine.summary()
    engine.close()


def test_admit_blocked_counts_steps_the_pool_held_the_queue_head(no_files):
    """A pool too small for two long prompts: the second waits for
    blocks, not for the lane, and every such step is counted."""
    model, params = _setup()
    engine = ServingEngine(model, params, num_slots=3, prefill_bucket=16,
                           block_size=8, num_blocks=9, prefix_cache=False)
    a, b = (engine.submit(p, max_new_tokens=6) for p in _prompts(40, 40))
    engine.run_until_idle()
    assert a.finish_reason == b.finish_reason == "length"
    assert engine.summary()["admit_blocked"] >= 1
    assert b.admit_time >= a.finish_time
    engine.close()


# ----------------------------------------------------------------------
# the router


def test_router_steps_are_spans_with_the_engine_underneath(no_files):
    model, params = _setup()
    router = ReplicaRouter(model, params, replicas=1,
                           engine_kwargs=ENGINE_KW, warmup_lens=(16,))
    router.warmup()
    spans.ring().clear()
    reqs = [router.submit(p, max_new_tokens=4) for p in _prompts(20, 5)]
    router.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    snap = spans.snapshot()
    got = _parents(snap)
    want = {
        "serve/submit": {None},
        "serve/router_step": {None},
        "serve/router_health": {"serve/router_step"},
        "serve/router_dispatch": {"serve/router_step"},
        "serve/dispatch": {"serve/router_dispatch"},
        "serve/replica_step": {"serve/router_step"},
        "serve/router_reap": {"serve/router_step"},
        "serve/engine_step": {"serve/replica_step"},
        "serve/admit": {"serve/engine_step"},
    }
    assert {k: got.get(k) for k in want} == want
    placed = [s for s in snap if s.name == "serve/dispatch"]
    assert [s.ids["request"] for s in placed] == [r.id for r in reqs]
    assert all(s.ids["replica"] == 0 for s in placed)
    # the router's id and the engine's id of one request, side by side
    engine_ids = {s.ids["request"] for s in snap
                  if s.name == "serve/start_prefill"}
    assert {s.ids["engine_request"] for s in placed} == engine_ids
    assert [s.ids["request"] for s in snap
            if s.name == "serve/submit"] == [r.id for r in reqs]
    steps = [s for s in snap if s.name == "serve/router_step"]
    assert [s.ids["step"] for s in steps] == list(range(
        steps[0].ids["step"], steps[0].ids["step"] + len(steps)))
    assert all(s.ids == {"replica": 0} for s in snap
               if s.name == "serve/replica_step")
    router.close()


def test_wait_for_the_prefill_lane_is_queue_not_prefill(tmp_path):
    """A short request behind a long prompt: the time it waited for the
    one prefill lane is under `queue` in its critical path, and `prefill`
    runs from its admission."""
    model, params = _setup()
    router = ReplicaRouter(model, params, replicas=1,
                           engine_kwargs=ENGINE_KW, warmup_lens=(16, 32),
                           telemetry_dir=str(tmp_path), trace=True)
    router.warmup()
    long_rr, short_rr = (router.submit(p, max_new_tokens=6)
                         for p in _prompts(56, 5))
    router.step()
    long_h, short_h = long_rr._handle, short_rr._handle
    router.run_until_idle()
    router.close()
    assert short_h.admit_time >= long_h.first_token_time
    wait = short_h.admit_time - short_h.submit_time
    span_s = short_h.first_token_time - short_h.admit_time
    assert wait > 0
    paths = {p["request"]: p for p in critical_paths(read_trace(tmp_path))}
    p = paths[short_rr.id]
    assert p["connected"]
    assert p["prefill_s"] == pytest.approx(span_s, abs=2e-4)
    assert p["queue_s"] >= wait - 2e-4
    # the stages still tile the request's life
    total = short_rr.finish_time - short_rr.submit_time
    stages = ("queue", "admission", "prefill", "handoff", "decode", "stall")
    assert sum(p[f"{k}_s"] for k in stages) == pytest.approx(total,
                                                             abs=1e-3)
    rows = [r for r in read_trace(tmp_path) if r.get("where") == "engine"]
    assert len(rows) == 2 and all(r["stage"] == "queue" for r in rows)
    # with a directory the ring is dumped, the replica's own spans in it
    names = {e["name"] for e in json.loads(
        (tmp_path / "spans_rank0.trace.json").read_text())["traceEvents"]}
    assert {"serve/router_step", "serve/engine_step",
            "serve/deliver"} <= names


# ----------------------------------------------------------------------
# the Trainer


def test_train_step_is_a_span_with_h2d_and_dispatch_under_it(no_files):
    import optax

    from pytorchdistributed_tpu.models import MLP
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    trainer = Trainer(MLP(features=(16, 4)), optax.sgd(0.1), mse_loss,
                      mesh=create_mesh(data=8), strategy="dp",
                      log_every=10 ** 9, watchdog=False)
    assert trainer.telemetry_dir is None
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((16, 8)).astype(np.float32),
             "y": rng.standard_normal((16, 4)).astype(np.float32)}
    for _ in range(3):
        loss = trainer.train_step(batch)["loss"]
    float(loss)
    snap = spans.snapshot()
    got = _parents(snap)
    assert got["train/step"] == {None}
    assert got["train/h2d"] == {"train/step"}
    assert got["train/compile_and_dispatch"] == {"train/step"}
    assert got["train/dispatch"] == {"train/step"}
    assert got["train/init_state"] == {"train/step"}
    steps = [s for s in snap if s.name == "train/step"]
    assert [s.ids["step"] for s in steps] == [1, 2, 3]
    # the first shape compiles, the later dispatches do not
    assert sum(s.name == "train/compile_and_dispatch" for s in snap) == 1
    assert sum(s.name == "train/dispatch" for s in snap) == 2

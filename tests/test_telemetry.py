"""Telemetry subsystem tests (ISSUE 2): span tracer round-trip, the
StepAccounting join against hand-computed numbers, anomaly tripwires on
injected NaNs, and the run-report CLI end-to-end — all on the CPU sim."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from pytorchdistributed_tpu.telemetry import (
    AnomalyDetector,
    EventLog,
    SpanTracer,
    StepAccounting,
    merge_chrome_traces,
    peak_flops_for,
    read_events,
    summarize_new_events,
)
from pytorchdistributed_tpu.telemetry.accounting import (
    CPU_SIM_NOMINAL_PEAK_FLOPS,
)
from pytorchdistributed_tpu.telemetry.report import render
from pytorchdistributed_tpu.utils.hlo import collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# span tracer


def test_span_tracer_chrome_roundtrip(tmp_path):
    """Spans dump as valid Chrome-trace JSON (X events, µs ts/dur, pid =
    rank) and merge across ranks onto one timeline."""
    for rank in (0, 1):
        tr = SpanTracer(rank=rank)
        with tr.span("train/data_load"):
            time.sleep(0.001)
        with tr.span("train/dispatch"):
            pass
        tr.dump(tmp_path / f"spans_rank{rank}.trace.json")

    raw = json.loads((tmp_path / "spans_rank0.trace.json").read_text())
    events = raw["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"train/data_load", "train/dispatch"}
    for e in xs:
        assert e["pid"] == 0 and e["dur"] >= 0 and e["ts"] > 0
    # the 1 ms sleep is visible in µs
    dl = next(e for e in xs if e["name"] == "train/data_load")
    assert dl["dur"] >= 1000
    # metadata names the rank process
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["args"]["name"] == "host rank 0" for e in meta)

    merged = merge_chrome_traces(tmp_path)
    assert {e["pid"] for e in merged["traceEvents"]
            if e["ph"] == "X"} == {0, 1}


def test_span_tracer_ring_buffer_bounds_memory():
    tr = SpanTracer(capacity=8, rank=0)
    for i in range(100):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 8
    names = {e["name"] for e in tr.to_chrome_trace()["traceEvents"]
             if e["ph"] == "X"}
    assert names == {f"s{i}" for i in range(92, 100)}  # oldest evicted


def test_span_totals():
    tr = SpanTracer(rank=0)
    for _ in range(3):
        with tr.span("a"):
            pass
    totals = tr.totals()
    assert totals["a"][1] == 3 and totals["a"][0] >= 0


def test_span_overhead_under_budget():
    """The <1%-of-step-time acceptance: the Trainer opens ~4 spans/step,
    an engine step ~10, a router step 5 more; even a 5 ms sim step
    grants 50 µs/step at 1%. Budget each span at 10 µs (measured ~2-3 µs
    here: parent, ids and the profiler annotation included, no capture
    running). The span's own cost is CPU time of this thread
    (`time.thread_time`), not the wall clock of a core that five other
    xdist workers share; best of five."""
    from pytorchdistributed_tpu.telemetry import span

    n = 2000
    trials = []
    for _ in range(5):
        t0 = time.thread_time()
        for i in range(n):
            with span("x", request=i, step=7):
                pass
        trials.append((time.thread_time() - t0) / n)
    per_span = min(trials)
    assert per_span < 10e-6, f"span overhead {per_span * 1e6:.1f} µs"


# ---------------------------------------------------------------------------
# accounting


def test_collective_bytes_parses_shapes():
    hlo = textwrap.dedent("""\
        %all-reduce.1 = f32[16,8]{1,0} all-reduce(f32[16,8]{1,0} %dot.3), channel_id=2
        %all-reduce.2 = f32[] all-reduce(f32[] %reduce), channel_id=3
        %ag = (bf16[4,8]{1,0}, bf16[32,8]{1,0}) all-gather-start(bf16[4,8]{1,0} %p), dimensions={0}
        %agd = bf16[32,8]{1,0} all-gather-done((bf16[4,8]{1,0}, bf16[32,8]{1,0}) %ag)
        %cp = s8[128]{0} collective-permute(s8[128]{0} %x), source_target_pairs={{0,1}}
        %cps = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(f32[64]{0} %y), source_target_pairs={{0,1}}
        %ars = (f32[10]{0}, f32[20]{0}) all-reduce-start(f32[10]{0} %a, f32[20]{0} %b), channel_id=9
        %agv = ((f32[4]{0}, f32[6]{0}), (f32[16]{0}, f32[24]{0})) all-gather-start(f32[4]{0} %c, f32[6]{0} %d), dimensions={0}
        %fusion.9 = f32[16,8]{1,0} fusion(f32[16,8]{1,0} %p2, f32[16,8]{1,0} %all-reduce.1), kind=kLoop
    """)
    by_op = collective_bytes(hlo)
    # two sync all-reduces + the variadic -start whose tuple IS its
    # result set (both elements count)
    assert by_op["all-reduce"] == 16 * 8 * 4 + 4 + (10 + 20) * 4
    # all-gather-start staging tuples bill element [1] only: the result
    # array for the flat form, the nested result tuple for the variadic
    assert by_op["all-gather"] == 32 * 8 * 2 + (16 + 24) * 4
    # sync permute counts its array; the TPU async form's staging tuple
    # (operand, result, context u32[] tokens) bills element [1] — the
    # result — not the trailing 4-byte context token
    assert by_op["collective-permute"] == 128 + 64 * 4
    assert by_op["all-to-all"] == 0                    # -done never counted


def test_peak_flops_lookup():
    peak, src = peak_flops_for("TPU v5 lite")
    assert peak == 197e12 and src == "TPU v5 lite"
    peak, src = peak_flops_for("cpu", "cpu")
    assert peak == CPU_SIM_NOMINAL_PEAK_FLOPS and src == "cpu-sim-nominal"
    peak, src = peak_flops_for("TPU v99")
    assert peak is None and src.startswith("unknown")


def test_step_accounting_math_roundtrip(tmp_path):
    acct = StepAccounting(
        model_flops_per_step=2e11, comm_bytes_per_step=1024,
        comm_bytes_by_op={"all-reduce": 1024}, tokens_per_step=8192,
        samples_per_step=8, peak_flops_per_device=1e12,
        peak_source="cpu-sim-nominal", n_devices=8)
    # hand-computed: 2e11 flops in 0.5 s on a 1e12 peak = 40% MFU
    assert acct.mfu(0.5) == pytest.approx(0.4)
    assert acct.tokens_per_s(0.5) == pytest.approx(16384.0)
    assert acct.comm_bytes_per_s(0.5) == pytest.approx(2048.0)
    assert acct.mfu(0.0) is None
    acct.save(tmp_path / "accounting.json")
    assert StepAccounting.load(tmp_path / "accounting.json") == acct


def _mlp_trainer(telemetry_dir=None):
    import optax

    from pytorchdistributed_tpu.models import MLP
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    return Trainer(
        MLP(features=(16, 4)), optax.sgd(0.1), mse_loss,
        mesh=create_mesh(data=8), strategy="dp", log_every=2,
        watchdog=True,
        telemetry_dir=str(telemetry_dir) if telemetry_dir else None)


def _mlp_batch(nan=False):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 8)).astype(np.float32)
    if nan:
        x[0, 0] = np.nan
    return {"x": x, "y": rng.standard_normal((16, 4)).astype(np.float32)}


def test_step_accounting_mlp_hand_computed():
    """The 8-dev DDP MLP is small enough to account for by hand: the dp
    gradient all-reduces must move exactly the parameter bytes (W1 8x16 +
    b1 16 + W2 16x4 + b2 4 = 212 params x 4 B) plus the 4-byte scalar
    loss all-reduce; tokens = samples (no "tokens" leaf); MFU divides the
    cost-analysis flops by the sim's nominal peak."""
    trainer = _mlp_trainer()
    acct = trainer.step_accounting(_mlp_batch())
    param_bytes = (8 * 16 + 16 + 16 * 4 + 4) * 4
    assert acct.comm_bytes_by_op["all-reduce"] == param_bytes + 4
    assert acct.comm_bytes_per_step == param_bytes + 4
    assert acct.peak_source == "cpu-sim-nominal"
    assert acct.n_devices == 8
    assert acct.tokens_per_step == 16 and acct.samples_per_step == 16
    # flops are PER DEVICE (post-partitioning): per-device batch is
    # 16/8 = 2, fwd matmuls 2·b·(8·16+16·4), fwd+bwd ≥ 3x that
    assert acct.model_flops_per_step >= 3 * 2 * (16 // 8) * (8 * 16
                                                             + 16 * 4)
    assert acct.mfu(1.0) == pytest.approx(
        round(acct.model_flops_per_step / CPU_SIM_NOMINAL_PEAK_FLOPS, 4))


def test_step_accounting_counts_lm_tokens():
    from pytorchdistributed_tpu.telemetry.accounting import (
        _batch_tokens_samples,
    )

    tokens, samples = _batch_tokens_samples(
        {"tokens": np.zeros((4, 128), np.int32),
         "targets": np.zeros((4, 128), np.int32)})
    assert tokens == 512 and samples == 4


# ---------------------------------------------------------------------------
# events / tripwires


def test_anomaly_detector_non_finite_and_spike():
    det = AnomalyDetector(warmup=3, z_threshold=6.0)
    # warmup: steady loss, no events
    for step in range(5):
        assert det.check({"loss": 1.0 + 0.01 * step}, step=step) == []
    found = det.check({"loss": 100.0}, step=6)
    assert [k for k, _ in found] == ["loss_spike"]
    assert found[0][1]["z"] > 6.0
    found = det.check({"loss": float("nan"), "grad_norm": float("inf")},
                      step=7)
    kinds = sorted(k for k, _ in found)
    assert kinds == ["non_finite_metric", "non_finite_metric"]
    # a loss DROP is not an anomaly (one-sided tripwire)
    assert det.check({"loss": 0.0}, step=8) == []


def test_event_log_roundtrip_and_agent_summary(tmp_path):
    with EventLog(tmp_path / "events_rank1.jsonl", rank=1) as log:
        log.emit("loss_spike", step=30, z=7.1)
        log.emit("non_finite_metric", step=40, metric="loss", value="nan")
    events = read_events(tmp_path)
    assert [e.kind for e in events] == ["loss_spike", "non_finite_metric"]
    assert events[0].rank == 1 and events[0].step == 30
    assert events[0].data["z"] == 7.1
    offsets: dict = {}
    summary = summarize_new_events(tmp_path, offsets)
    assert "rank 1 loss_spike x1" in summary
    assert "rank 1 non_finite_metric x1" in summary
    # offsets advanced: a second sweep sees nothing new
    assert summarize_new_events(tmp_path, offsets) is None


class _FakeLoader:
    """Minimal loader protocol (set_epoch/len/batch_size/iter) over a
    fixed batch list."""

    def __init__(self, batches):
        self._batches = batches
        self.batch_size = batches[0]["x"].shape[0]

    def set_epoch(self, epoch):
        pass

    def __len__(self):
        return len(self._batches)

    def __iter__(self):
        return iter([dict(b) for b in self._batches])


def test_tripwires_fire_on_injected_nan_loss(tmp_path):
    """NaN batch → at log cadence the tripwire writes a durable
    non_finite_metric event BEFORE the watchdog raises; the report folds
    the event in afterwards (the post-mortem the watchdog alone never
    left behind)."""
    run_dir = tmp_path / "run"
    trainer = _mlp_trainer(run_dir)
    batches = [_mlp_batch(), _mlp_batch(nan=True)]  # log_every=2
    with pytest.raises(FloatingPointError):
        trainer.run_epoch(_FakeLoader(batches), epoch=0)
    events = read_events(run_dir)
    assert any(e.kind == "non_finite_metric" and e.data["metric"] == "loss"
               for e in events)
    # the exception path still dumped spans + flushed sinks (run_epoch
    # teardown): the report renders from a crashed run
    out = render(run_dir)
    assert "non_finite_metric" in out


# ---------------------------------------------------------------------------
# end-to-end: train with telemetry on, then report


def test_telemetry_smoke_end_to_end(tmp_path):
    """The quick-tier smoke: an 8-device DDP MLP run with telemetry on
    leaves a complete run dir — per-rank metrics with step time / MFU /
    comm-bytes, a valid span trace, accounting.json — and the report CLI
    renders all of it."""
    run_dir = tmp_path / "run"
    trainer = _mlp_trainer(run_dir)
    loader = _FakeLoader([_mlp_batch() for _ in range(8)])
    trainer.fit(loader, max_epochs=1)

    rows = [json.loads(line) for line in
            (run_dir / "metrics_rank0.jsonl").read_text().splitlines()]
    assert len(rows) == 4  # 8 steps, log_every=2
    tail = rows[-1]  # first rows may predate the meter warmup
    for key in ("loss", "samples_per_s", "step_time_s", "tokens_per_s",
                "mfu", "comm_bytes_per_step"):
        assert key in tail, (key, tail)
    assert tail["comm_bytes_per_step"] == 852  # MLP hand-computed value

    spans = json.loads(
        (run_dir / "spans_rank0.trace.json").read_text())["traceEvents"]
    names = {e["name"] for e in spans if e["ph"] == "X"}
    assert {"train/data_load", "train/step", "train/h2d",
            "train/compile_and_dispatch", "train/dispatch",
            "train/metric_sync"} <= names

    assert (run_dir / "accounting.json").exists()
    out = render(run_dir)
    assert "step accounting" in out and "sim fallback" in out
    assert "tokens/s" in out and "mfu" in out and "comm" in out
    assert "tripwire events: none" in out
    assert "host spans" in out and "train/dispatch" in out


def test_report_step_time_fallback_spans_epochs():
    """Without step_time_s rows (no accounting), the report derives step
    time from row timestamps — and step numbers reset per epoch, so a
    2-epoch run must not divide by last-minus-first step."""
    from pytorchdistributed_tpu.telemetry.report import _derive_step_time

    rows = [{"time": 100.0, "epoch": 0, "step": 2},
            {"time": 102.0, "epoch": 0, "step": 4},
            {"time": 104.0, "epoch": 1, "step": 2},
            {"time": 106.0, "epoch": 1, "step": 4}]
    # 6s wall over 2 + 2 + 2 = 6 steps -> 1 s/step (naive s1-s0 would
    # see (4-2)=2 steps and report 3 s/step)
    assert _derive_step_time(rows) == pytest.approx(1.0)
    # a run ending on the same step number it started on still answers
    assert _derive_step_time(rows[1:3]) == pytest.approx(1.0)
    assert _derive_step_time(rows[:1]) is None
    # explicit step_time_s rows win over the derivation
    assert _derive_step_time(
        [dict(r, step_time_s=0.5) for r in rows]) == pytest.approx(0.5)


def test_accounting_built_on_restored_trainer(tmp_path):
    """A trainer whose state arrived via restore() (a relaunched
    incarnation) must still build StepAccounting — the crash-recovery
    runs are exactly the ones telemetry post-mortems."""
    import optax

    from pytorchdistributed_tpu.models import MLP
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    ckpt = tmp_path / "ckpt"
    loader = _FakeLoader([_mlp_batch() for _ in range(4)])
    first = Trainer(MLP(features=(16, 4)), optax.sgd(0.1), mse_loss,
                    mesh=create_mesh(), checkpoint_dir=str(ckpt),
                    log_every=2, watchdog=False)
    first.fit(loader, max_epochs=1)

    run_dir = tmp_path / "run"
    resumed = Trainer(MLP(features=(16, 4)), optax.sgd(0.1), mse_loss,
                      mesh=create_mesh(), checkpoint_dir=str(ckpt),
                      log_every=2, watchdog=False,
                      telemetry_dir=str(run_dir))
    resumed.restore(_mlp_batch())
    assert resumed.accounting is None  # init() never ran
    resumed.run_epoch(loader, epoch=1)
    assert resumed.accounting is not None
    assert (run_dir / "accounting.json").exists()
    rows = [json.loads(line) for line in
            (run_dir / "metrics_rank0.jsonl").read_text().splitlines()]
    assert "mfu" in rows[-1] and "comm_bytes_per_step" in rows[-1]


def test_report_cli_subcommands(tmp_path):
    """Argument surface of `python -m pytorchdistributed_tpu.telemetry`:
    report renders an empty dir without crashing; merge-trace writes a
    merged chrome trace."""
    from pytorchdistributed_tpu.telemetry.__main__ import main

    tr = SpanTracer(rank=0)
    with tr.span("a"):
        pass
    tr.dump(tmp_path / "spans_rank0.trace.json")
    assert main(["report", str(tmp_path)]) == 0
    assert main(["merge-trace", str(tmp_path)]) == 0
    merged = json.loads((tmp_path / "merged.trace.json").read_text())
    assert any(e.get("name") == "a" for e in merged["traceEvents"])


def test_report_cli_two_process_run(tmp_path):
    """The acceptance scenario: a REAL 2-process CPU-sim training run
    (launched through the run.py agent with --telemetry-dir) leaves
    per-rank telemetry, and the report CLI prints a merged per-rank
    report with step time, tokens/s, MFU (sim fallback), comm-bytes/step
    and the tripwire section."""
    run_dir = tmp_path / "telemetry"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import optax
        from pytorchdistributed_tpu.data import (
            DataLoader, SyntheticTokenDataset)
        from pytorchdistributed_tpu.models import GPT2, gpt2_config
        from pytorchdistributed_tpu.runtime import dist
        from pytorchdistributed_tpu.runtime.mesh import create_mesh
        from pytorchdistributed_tpu.training import (
            Trainer, token_cross_entropy_loss)

        dist.init_process_group()
        cfg = gpt2_config("test", num_layers=2, max_seq_len=32,
                          vocab_size=128)
        ds = SyntheticTokenDataset(size=64, seq_len=32, vocab_size=128,
                                   seed=0)
        loader = DataLoader(ds, batch_size=8,
                            num_replicas=dist.get_world_size(),
                            rank=dist.get_rank())
        tr = Trainer(GPT2(cfg), optax.adamw(1e-3),
                     token_cross_entropy_loss, mesh=create_mesh(),
                     log_every=2, watchdog=False)
        assert tr.telemetry_dir is not None  # from PTD_TELEMETRY_DIR
        tr.fit(loader, max_epochs=1)
        dist.destroy_process_group()
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--devices-per-proc", "1",
         "--telemetry-dir", str(run_dir), str(script)],
        cwd=REPO, timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr

    report = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.telemetry",
         "report", str(run_dir)],
        cwd=REPO, timeout=120, capture_output=True, text=True)
    assert report.returncode == 0, report.stderr
    out = report.stdout
    assert "ranks: 0, 1" in out
    assert "step time" in out and "tokens/s" in out and "mfu" in out
    assert "comm" in out and "sim fallback" in out
    assert "tripwire events" in out
    # both ranks logged real rows
    for rank in (0, 1):
        rows = (run_dir / f"metrics_rank{rank}.jsonl").read_text()
        assert "tokens_per_s" in rows and "comm_bytes_per_step" in rows


def test_report_merges_two_launched_ranks(tmp_path):
    """Ungated 2-process variant (this jaxlib cannot do cross-process CPU
    collectives, so the gated test above skips): two run.py-launched
    workers each train their own 4-device sim replica with telemetry from
    the env contract — per-rank files must NOT collide (the RANK-env
    fallback) and the report merges both ranks."""
    run_dir = tmp_path / "telemetry"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import optax
        from pytorchdistributed_tpu.models import MLP
        from pytorchdistributed_tpu.runtime.mesh import create_mesh
        from pytorchdistributed_tpu.training import Trainer, mse_loss

        class Loader:
            batch_size = 16
            def set_epoch(self, e): pass
            def __len__(self): return 6
            def __iter__(self):
                rng = np.random.default_rng(0)
                for _ in range(6):
                    yield {{"x": rng.standard_normal((16, 8)).astype(
                               np.float32),
                           "y": rng.standard_normal((16, 4)).astype(
                               np.float32)}}

        tr = Trainer(MLP(features=(16, 4)), optax.sgd(0.1), mse_loss,
                     mesh=create_mesh(), log_every=2, watchdog=False)
        assert tr.telemetry_dir is not None
        tr.fit(Loader(), max_epochs=1)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--devices-per-proc", "4",
         "--telemetry-dir", str(run_dir), str(script)],
        cwd=REPO, timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    for rank in (0, 1):  # distinct per-rank files, no clobbering
        assert (run_dir / f"metrics_rank{rank}.jsonl").exists()
        assert (run_dir / f"spans_rank{rank}.trace.json").exists()
    report = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.telemetry",
         "report", str(run_dir)],
        cwd=REPO, timeout=120, capture_output=True, text=True)
    assert report.returncode == 0, report.stderr
    out = report.stdout
    assert "ranks: 0, 1" in out
    assert "step time" in out and "tokens/s" in out and "mfu" in out
    assert "comm" in out and "sim fallback" in out
    assert "tripwire events" in out


def test_run_agent_aggregates_events(tmp_path):
    """The run.py agent prints a per-incarnation tripwire summary next to
    its restart decisions when --telemetry-dir is set."""
    run_dir = tmp_path / "telemetry"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {REPO!r})
        from pytorchdistributed_tpu.telemetry import EventLog
        log = EventLog.from_env(rank=int(os.environ["RANK"]))
        assert log is not None, "agent did not export PTD_TELEMETRY_DIR"
        log.emit("loss_spike", step=10, z=8.5)
        log.close()
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--telemetry-dir", str(run_dir),
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "[run] telemetry:" in proc.stderr, proc.stderr
    assert "loss_spike x1" in proc.stderr, proc.stderr

"""Config / flag system (SURVEY.md §5: "dataclass configs + CLI overrides;
a --backend/mesh flag selecting {cpu-sim, single-TPU, pod}" — the north
star's "entrypoints select the TPU backend via a flag").

The reference's whole config surface is two argparse flags
(--max_epochs/--batch_size, ddp_gpus.py:88-92) with topology implied by
`torch.cuda.device_count()`. Here one dataclass covers model choice,
parallelism axes, precision and training hyperparameters; any field is
overridable from the CLI (`--field value`), and `PRESETS` carries the five
BASELINE.json benchmark configs.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any


@dataclasses.dataclass
class ExperimentConfig:
    # model
    model: str = "gpt2"            # gpt2 | llama | bert | vit | resnet18 | resnet50 | mlp
    model_size: str = "test"       # per-family size preset
    attention: str = "dense"       # dense | pallas | ring | ulysses
    remat: bool = False
    fused_norms: bool = False      # custom_vjp norm backward (opt-in until
    #                                the chip A/B lands — BASELINE.md r4)
    # parallelism (mesh axis sizes; -1 = absorb remaining devices)
    strategy: str = "dp"           # dp | fsdp | tp | tp_fsdp | auto
    device_memory_gb: float = 0.0  # per-chip HBM for --strategy auto
                                   # (0 = query the device, v5e fallback)
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    pipe: int = 1
    seq: int = 1
    num_slices: int = 1
    pipeline_microbatches: int = 1
    # Gradient accumulation: split each global batch into this many
    # micro-batches inside the jitted step (fp32 grad sum, one optimizer
    # update) — the large-batch recipe when activations exceed HBM.
    accum_steps: int = 1
    mlm_mask_rate: float = 0.15    # BERT dynamic-masking rate
    dropout_rate: float = 0.0      # transformer-family dropout (training
    #                                only; losses wire the rng stream)
    pp_schedule: str = "gpipe"     # gpipe | 1f1b (transformer models)
    expert: int = 1                # mesh axis for expert parallelism
    moe_experts: int = 0           # >0: Switch-MoE MLPs (transformer models)
    moe_capacity_factor: float = 1.25  # expert slot headroom over the
    #                                    uniform-routing load (GShard's cf)
    moe_top_k: int = 1             # routed experts per token (1 = Switch,
    #                                2 = GShard top-2 with gate renorm)
    moe_every: int = 1             # MoE block cadence: every Nth block is
    #                                MoE, others dense (needs unrolled
    #                                layers when > 1)
    moe_chunks: int = 1            # capacity chunks for dispatch/combine
    #                                a2a <-> expert-matmul overlap (>1
    #                                pipelines the exchange)
    # precision
    bf16: bool = True
    # Int8 quantized-training matmuls (ops/quant.py, the amp→bf16→int8
    # axis): "int8_fwd" quantizes forward weight matmuls (bf16 backward,
    # the safe default for the MXU's ~2x int8 rate), "int8" also
    # quantizes the backward with stochastic rounding on the gradient.
    # Applies to the transformer families' QKV/out/MLP/LM-head (and
    # fused-CE) contractions plus the MLP toy; implies bf16 compute.
    quant: str = "none"            # none | int8_fwd | int8
    # Collective-latency hiding (ops/overlap.py + trainer scheduler
    # flags): "xla" = monolithic collectives + XLA latency-hiding
    # scheduler (default), "ring" = decomposed collective-matmul rings on
    # the TP projections too, "off" = neither (the measured baseline).
    overlap: str = "xla"           # ring | xla | off
    # training
    max_epochs: int = 1
    batch_size: int = 32           # per-process
    learning_rate: float = 1e-3
    optimizer: str = "adamw"       # adamw | sgd | adafactor
    weight_decay: float = 0.01     # adamw decay, masked to ndim>=2 params
    # LR schedule: peak = learning_rate, linear warmup over warmup_steps,
    # then constant / cosine / linear decay to lr_end over decay_steps.
    lr_schedule: str = "constant"  # constant | cosine | linear
    warmup_steps: int = 0
    decay_steps: int = 10_000      # decay horizon (cosine/linear)
    lr_end: float = 0.0
    grad_clip_norm: float = 0.0    # clip_by_global_norm; 0 = off
    seed: int = 0
    # data: real on-disk datasets when data_dir is set and populated
    # (CIFAR-10 pickle batches or {split}_images/labels.npy pairs —
    # data/files.py); synthetic fallback otherwise
    data_dir: str = ""
    dataset_size: int = 2048       # synthetic dataset size
    seq_len: int = 128
    image_size: int = 32
    num_classes: int = 10
    # infra
    backend: str = "auto"          # auto | tpu | cpu-sim<N>
    checkpoint_dir: str = ""
    checkpoint_every_steps: int = 0
    resume: bool = False
    log_every: int = 10
    profile_dir: str = ""          # capture a jax.profiler trace here
    metrics_file: str = ""         # rank-0 JSONL per-step metrics sink
    watchdog: bool = True          # NaN/Inf watchdog at log cadence
    # In-graph training diagnostics (telemetry/diagnostics.py):
    # "off" | "scalars" | "full[:N]" — per-layer activation/grad health,
    # NaN provenance, int8 saturation, all as extra jitted outputs of
    # the same compiled step. Empty = unset, so the PTD_DIAGNOSTICS env
    # contract (run.py workers) still applies; any explicit value wins.
    diagnostics: str = ""
    # Speculative decoding for the serving path (serving/engine.py,
    # ISSUE 8): spec_k > 0 makes every decode tick draft-and-verify that
    # many tokens per target forward (lossless rejection sampling —
    # greedy output bitwise-equal, sampled distribution-equal).
    # draft_layers > 0 builds the draft by truncating the served model
    # to its first N layers (inference.truncated_draft); 0 self-drafts
    # with the full model. Serving-only knobs: training ignores them
    # (examples/serve.py --spec-k/--draft-layers consume the pair).
    spec_k: int = 0
    draft_layers: int = 0


# The five BASELINE.json benchmark configs, smallest to largest.
PRESETS: dict[str, dict[str, Any]] = {
    # configs[0]: ResNet-18 / CIFAR-10 CPU smoke (the "gloo smoke" analog)
    "resnet18_cifar_smoke": dict(
        model="resnet18", backend="cpu-sim8", image_size=32, num_classes=10,
        strategy="dp", batch_size=32, bf16=False),
    # configs[1]: ResNet-50 / ImageNet multi-process DP
    "resnet50_imagenet_dp": dict(
        model="resnet50", image_size=224, num_classes=1000, strategy="dp",
        batch_size=64),
    # configs[2]: BERT-base MLM, bf16 (warmup+linear decay, the BERT recipe)
    "bert_base_mlm": dict(
        model="bert", model_size="base", seq_len=512, strategy="dp",
        batch_size=16, bf16=True, learning_rate=1e-4, lr_schedule="linear",
        warmup_steps=1000, decay_steps=100_000, grad_clip_norm=1.0),
    # configs[3]: GPT-2-medium FSDP + activation checkpointing
    # (warmup-cosine + clipping, the GPT recipe)
    "gpt2_medium_fsdp": dict(
        model="gpt2", model_size="medium", seq_len=1024, strategy="fsdp",
        data=1, fsdp=-1, remat=True, batch_size=8, learning_rate=3e-4,
        lr_schedule="cosine", warmup_steps=500, decay_steps=50_000,
        grad_clip_norm=1.0),
    # configs[4]: ViT-L/16 multi-host DP across pod slices
    "vit_l16_multihost": dict(
        model="vit", model_size="large", image_size=224, num_classes=1000,
        strategy="dp", num_slices=2, batch_size=32),
}


def select_backend(backend: str) -> None:
    """Apply the --backend flag. MUST run before the first JAX backend
    initialization (any jax.devices() call)."""
    import re

    if backend == "auto":
        return
    if backend == "tpu":
        # a requirement, not a preference: pin the platform so autodetect
        # cannot land on the CPU, then make the chip answer here
        import jax
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            os.environ["JAX_PLATFORMS"] = "tpu"
            jax.config.update("jax_platforms", "tpu")
        try:
            found = jax.default_backend()
        except RuntimeError as e:
            raise RuntimeError(f"--backend tpu: no TPU backend ({e})") from e
        if found != "tpu":
            raise RuntimeError(
                f"--backend tpu: JAX is already running on {found!r}")
        return
    if backend.startswith("cpu-sim"):
        n = int(backend[len("cpu-sim"):] or "8")
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        # replace (not keep) any pre-existing count: the explicit backend
        # request wins over inherited env
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       flags)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
        try:
            import jax
            jax.config.update("jax_platforms", "cpu")
        except ImportError:
            pass
        return
    raise ValueError(f"unknown backend {backend!r} "
                     "(use auto | tpu | cpu-sim<N>)")


def parse_cli(argv=None) -> ExperimentConfig:
    """Every dataclass field becomes a --flag; --preset applies a BASELINE
    config first, explicit flags override it."""
    parser = argparse.ArgumentParser(description="tpu-distributed training")
    parser.add_argument("--preset", choices=sorted(PRESETS), default=None)
    for f in dataclasses.fields(ExperimentConfig):
        if f.type == "bool":
            parser.add_argument(f"--{f.name}", type=lambda s: s.lower() in
                                ("1", "true", "yes"), default=None,
                                metavar="BOOL")
        else:
            parser.add_argument(f"--{f.name}",
                                type=type(f.default), default=None)
    ns = parser.parse_args(argv)
    values: dict[str, Any] = {}
    if ns.preset:
        values.update(PRESETS[ns.preset])
    for f in dataclasses.fields(ExperimentConfig):
        v = getattr(ns, f.name)
        if v is not None:
            values[f.name] = v
    return ExperimentConfig(**values)


def _build_model(cfg: ExperimentConfig):
    """(model, loss_fn, dataset) for a config — separated from `build` so
    the auto-placement path can re-instantiate the model after the planner
    picks a pipeline split."""
    import jax.numpy as jnp

    from pytorchdistributed_tpu import models
    from pytorchdistributed_tpu.data import (
        SyntheticImageDataset,
        SyntheticRegressionDataset,
        SyntheticTokenDataset,
    )
    from pytorchdistributed_tpu.training import (
        cross_entropy_loss,
        moe_token_cross_entropy_loss,
        mse_loss,
        token_cross_entropy_loss,
    )

    if cfg.moe_experts > 0:
        token_cross_entropy_loss = moe_token_cross_entropy_loss

    if cfg.quant not in ("none", "int8_fwd", "int8"):
        raise ValueError(f"unknown --quant {cfg.quant!r} "
                         "(none | int8_fwd | int8)")
    # quantized matmuls ride the bf16 compute dtype (the int8 path
    # rescales through fp32 either way; fp32 "compute" would only slow
    # the non-matmul remainder)
    dtype = jnp.bfloat16 if (cfg.bf16 or cfg.quant != "none") else jnp.float32
    from pytorchdistributed_tpu.parallel.overlap import validate_overlap

    validate_overlap(cfg.overlap)
    tkw = dict(attention=cfg.attention, remat=cfg.remat, dtype=dtype,
               quant=cfg.quant, overlap=cfg.overlap,
               fused_norms=cfg.fused_norms,
               pipeline_stages=cfg.pipe if cfg.pipe > 1 else 1,
               pipeline_microbatches=cfg.pipeline_microbatches,
               pp_schedule=cfg.pp_schedule, moe_experts=cfg.moe_experts,
               dropout_rate=cfg.dropout_rate)
    if cfg.moe_experts > 0:
        tkw.update(moe_capacity_factor=cfg.moe_capacity_factor,
                   moe_top_k=cfg.moe_top_k, moe_every=cfg.moe_every,
                   moe_chunks=cfg.moe_chunks,
                   # interleaving picks blocks by index — needs the
                   # unrolled stack (transformer.py __post_init__ errors
                   # on scan_layers + moe_every > 1)
                   **(dict(scan_layers=False) if cfg.moe_every > 1
                      else {}))

    lm_families = {
        "gpt2": (models.GPT2, models.gpt2_config),
        "llama": (models.Llama, models.llama_config),
        "bert": (models.BertMLM, models.bert_config),
    }
    if cfg.model in lm_families:
        cls, make_cfg = lm_families[cfg.model]
        model = cls(make_cfg(cfg.model_size, max_seq_len=cfg.seq_len, **tkw))
        loss = token_cross_entropy_loss
        data_vocab = model.cfg.vocab_size - (cfg.model == "bert")
        ds = _token_dataset(cfg, data_vocab)
        if cfg.model == "bert":
            # BERT trains the masked-LM objective, not next-token: wrap the
            # corpus in dynamic 80/10/10 masking (data/datasets.MLMDataset).
            # The top vocab id is RESERVED as [MASK]: the corpus (synthetic
            # or --data_dir) is held to ids < vocab-1 so mask positions are
            # unambiguous.
            from pytorchdistributed_tpu.data import MLMDataset

            ds = MLMDataset(ds, model.cfg.vocab_size,
                            mask_rate=cfg.mlm_mask_rate, seed=cfg.seed)
    elif cfg.model == "vit":
        model = models.ViT(models.vit_config(
            cfg.model_size, image_size=cfg.image_size,
            num_classes=cfg.num_classes, **tkw))
        loss = cross_entropy_loss
        ds = _image_dataset(cfg)
    elif cfg.model in ("resnet18", "resnet50"):
        maker = models.resnet18 if cfg.model == "resnet18" else models.resnet50
        model = maker(num_classes=cfg.num_classes, dtype=dtype,
                      **(dict(cifar_stem=True) if cfg.model == "resnet18"
                         and cfg.image_size <= 64 else {}))
        loss = cross_entropy_loss
        ds = _image_dataset(cfg)
    elif cfg.model == "mlp":
        from pytorchdistributed_tpu.ops.quant import dot_general_for

        model = models.MLP(dot_general=dot_general_for(cfg.quant))
        loss = mse_loss
        ds = SyntheticRegressionDataset(cfg.dataset_size, seed=cfg.seed)
    else:
        raise ValueError(f"unknown model {cfg.model!r}")
    return model, loss, ds


def _image_dataset(cfg: ExperimentConfig):
    """Real on-disk data when --data_dir points at a populated directory
    (CIFAR-10 pickle batches, or the {split}_images/labels.npy convention
    for ImageNet-class sets), synthetic fallback otherwise — the BASELINE
    img/s configs measure the real input pipeline when data is present."""
    from pytorchdistributed_tpu.data import SyntheticImageDataset
    from pytorchdistributed_tpu.data.files import load_cifar10, load_image_dir

    if cfg.data_dir:
        ds = (load_cifar10(cfg.data_dir) if cfg.image_size <= 32
              else load_image_dir(cfg.data_dir))
        if ds is None:
            ds = load_image_dir(cfg.data_dir) or load_cifar10(cfg.data_dir)
        if ds is not None:
            if ds.num_classes != cfg.num_classes:
                raise ValueError(
                    f"--data_dir dataset has {ds.num_classes} classes but "
                    f"the config expects {cfg.num_classes}")
            return ds
        print(f"[config] no dataset found under {cfg.data_dir!r}; "
              f"falling back to synthetic data", flush=True)
    return SyntheticImageDataset(cfg.dataset_size, cfg.image_size,
                                 num_classes=cfg.num_classes, seed=cfg.seed)


def _token_dataset(cfg: ExperimentConfig, vocab_size: int):
    """Real pre-tokenized corpus when --data_dir holds a
    ``{split}_tokens.npy`` (1-D stream or [n, seq+1] windows, memory-mapped
    through the native gather), synthetic fallback otherwise — the LM
    analog of _image_dataset."""
    from pytorchdistributed_tpu.data import SyntheticTokenDataset
    from pytorchdistributed_tpu.data.files import load_tokens

    if cfg.data_dir:
        ds = load_tokens(cfg.data_dir, cfg.seq_len)
        if ds is not None:
            if ds.vocab_size > vocab_size:
                hint = (" (the top id is reserved as [MASK] for BERT's "
                        "dynamic masking — remap it in the corpus)"
                        if cfg.model == "bert" else "")
                raise ValueError(
                    f"--data_dir corpus has token ids up to "
                    f"{ds.vocab_size - 1} but this config accepts data ids "
                    f"< {vocab_size}{hint}")
            return ds
        print(f"[config] no {{split}}_tokens.npy under {cfg.data_dir!r}; "
              f"falling back to synthetic data", flush=True)
    return SyntheticTokenDataset(cfg.dataset_size, cfg.seq_len,
                                 vocab_size, cfg.seed)


def build(cfg: ExperimentConfig):
    """(model, optimizer, loss_fn, mesh, dataset) from a config. Imports jax
    lazily so select_backend can act first. ``strategy="auto"`` runs the
    memory planner (parallel/auto.py — the device_map="auto" analog) and
    rewrites strategy + mesh axes from its plan."""
    from pytorchdistributed_tpu.runtime.mesh import MeshConfig, create_mesh

    if cfg.strategy == "auto":
        cfg = _auto_place(cfg)
    model, loss, ds = _build_model(cfg)
    mesh = create_mesh(MeshConfig(
        data=cfg.data, fsdp=cfg.fsdp, expert=cfg.expert, tensor=cfg.tensor,
        pipe=cfg.pipe, seq=cfg.seq, num_slices=cfg.num_slices))
    return model, make_optimizer(cfg), loss, mesh, ds, cfg


def _auto_place(cfg: ExperimentConfig) -> ExperimentConfig:
    """Run the auto-shard planner against the model's real abstract params
    (a scratch instantiation — nothing is allocated) and fold its
    (strategy, mesh axes) back into the config."""
    import dataclasses as _dc

    import jax
    import numpy as np

    from pytorchdistributed_tpu.parallel.auto import auto_shard

    model, _, ds = _build_model(cfg)
    sample = ds[np.arange(min(2, len(ds)))]
    inputs = next(sample[k] for k in ("x", "image", "tokens") if k in sample)
    mem = (cfg.device_memory_gb * 2**30) if cfg.device_memory_gb else None
    plan = auto_shard(model, (inputs,), n_devices=len(jax.devices()),
                      device_memory_bytes=mem, optimizer=cfg.optimizer)
    cfg = _dc.replace(
        cfg, strategy=plan.strategy, data=plan.mesh.data,
        fsdp=plan.mesh.fsdp, tensor=plan.mesh.tensor, pipe=plan.mesh.pipe)
    if plan.mesh.pipe > 1:
        cfg = _dc.replace(cfg, pipeline_microbatches=max(
            cfg.pipeline_microbatches, 2 * plan.mesh.pipe))
    return cfg


def make_lr_schedule(cfg: ExperimentConfig):
    """Scalar or optax schedule: linear warmup to the peak learning_rate
    over warmup_steps, then the configured decay (every BASELINE config past
    the smoke test trains with warmup+decay in practice)."""
    import optax

    lr, w = cfg.learning_rate, cfg.warmup_steps
    if cfg.lr_schedule == "constant":
        if w == 0:
            return lr
        return optax.schedules.warmup_constant_schedule(0.0, lr, w)
    if cfg.lr_schedule == "cosine":
        return optax.schedules.warmup_cosine_decay_schedule(
            0.0, lr, w, decay_steps=cfg.decay_steps, end_value=cfg.lr_end)
    if cfg.lr_schedule == "linear":
        warm = optax.schedules.linear_schedule(0.0, lr, max(w, 1))
        decay = optax.schedules.linear_schedule(
            lr, cfg.lr_end, max(cfg.decay_steps - w, 1))
        return optax.schedules.join_schedules([warm, decay], [w])
    raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r} "
                     "(constant | cosine | linear)")


def decay_mask(params):
    """Standard transformer weight-decay mask: decay matrices (kernels and
    embedding tables, ndim >= 2), never biases or norm scales (ndim <= 1) —
    decaying norm scales toward zero actively hurts. Shape-based so it
    works for every model family without name lists."""
    import jax

    return jax.tree.map(lambda p: getattr(p, "ndim", 0) >= 2, params)


def make_optimizer(cfg: ExperimentConfig):
    """Optimizer chain: [global-norm clip →] adamw/sgd/adafactor with the
    schedule; adamw's weight decay is masked to matrices only."""
    import optax

    lr = make_lr_schedule(cfg)
    if cfg.optimizer == "adamw":
        opt = optax.adamw(lr, weight_decay=cfg.weight_decay,
                          mask=decay_mask)
    elif cfg.optimizer == "sgd":
        opt = optax.sgd(lr, momentum=0.9)
    elif cfg.optimizer == "adafactor":
        # the memory-factored choice: second moment stored as row/col
        # factors — what lets 1B+ models train on one 16G chip
        opt = optax.adafactor(lr)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.grad_clip_norm > 0:
        opt = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), opt)
    return opt


def make_trainer(cfg: ExperimentConfig):
    """Fully-wired Trainer + DataLoader for a config."""
    from pytorchdistributed_tpu.data import DataLoader
    from pytorchdistributed_tpu.parallel.precision import Policy
    from pytorchdistributed_tpu.training import Trainer

    model, opt, loss, mesh, ds, cfg = build(cfg)
    loader = DataLoader(ds, batch_size=cfg.batch_size, seed=cfg.seed)
    if cfg.quant == "int8":
        precision = Policy.int8()
    elif cfg.quant == "int8_fwd":
        precision = Policy.int8_fwd()
    else:
        precision = Policy.bf16() if cfg.bf16 else Policy.full()
    trainer = Trainer(
        model, opt, loss, mesh=mesh, strategy=cfg.strategy,
        precision=precision,
        log_every=cfg.log_every,
        checkpoint_dir=cfg.checkpoint_dir or None,
        checkpoint_every_steps=cfg.checkpoint_every_steps,
        watchdog=cfg.watchdog,
        profile_dir=cfg.profile_dir or None,
        metrics_file=cfg.metrics_file or None,
        accum_steps=cfg.accum_steps,
        overlap=cfg.overlap,
        diagnostics=cfg.diagnostics or None,
    )
    return trainer, loader

"""Training launcher.

Two modes:

* ``--smoke`` (the default): the ``.reduced()`` variant of the chosen
  architecture — runs REAL steps and prints losses.  This is the
  end-to-end run used by examples/ and CI.
* ``--no-smoke``: the architecture at its published widths, with
  rematerialized layers, on whatever devices JAX finds (one TPU chip gives
  a 1x1 mesh, four give data=2 x model=2).

Host devices are forced only for a CPU run: ``--devices N`` (which also
pins ``JAX_PLATFORMS=cpu``), or ``JAX_PLATFORMS=cpu`` alone (8 devices).
On an accelerator nothing is forced, so a device that fails to start fails
the run instead of falling back to fake CPU devices.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --steps 30 --mode allgather --density 0.05 --devices 8
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --no-smoke --steps 5 --batch 4 --seq 1024 --density 0.01
"""
from __future__ import annotations

import argparse
import os


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--mode", default="allgather",
                    choices=["dense", "allgather", "shardedps"])
    ap.add_argument("--density", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "exact", "sampled", "blockwise"],
                    help="top-k compression engine (core/engine.py)")
    ap.add_argument("--quantize", default="none",
                    choices=["none", "bf16", "int8", "tern"],
                    help="wire quantization of sparse message values")
    ap.add_argument("--sampled-above", type=int, default=1 << 20,
                    help="auto engine: sampled threshold for leaves/rows "
                         "with at least this many elements")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the .reduced() variant (--no-smoke: the "
                         "published widths, rematerialized)")
    ap.add_argument("--devices", type=int, default=None,
                    help="run on N forced host (CPU) devices")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-level", default=None,
                    help="silence/route launcher output: debug | info | "
                         "warning | error (default: REPRO_LOG env or info)")
    ap.add_argument("--log-file", default=None,
                    help="mirror launcher output (timestamped) to a file")
    return ap.parse_args(argv)


def force_host_devices(devices: int | None) -> None:
    """Fake host devices for a CPU run, before JAX starts: ``devices``
    pins the CPU platform; ``JAX_PLATFORMS=cpu`` alone gets 8.  A set
    ``XLA_FLAGS`` is left alone."""
    if devices is not None:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={devices or 8}")


def build(args):
    """``(cfg, mesh, bundle)``: the mesh train step ``args`` asks for, on
    every device JAX was started with."""
    import jax

    from repro.configs import get_arch
    from repro.configs.shapes import InputShape, input_specs
    from repro.core.distributed import ExchangeConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch.steps import build_train_step

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    n_dev = jax.device_count()
    model_par = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = mesh_lib.make_mesh((n_dev // model_par, model_par),
                              ("data", "model"))
    shape = InputShape("smoke" if args.smoke else "full", args.seq,
                       args.batch, "train")
    ex_cfg = ExchangeConfig(mode=args.mode, density=args.density,
                            momentum=args.momentum, engine=args.engine,
                            quantize=args.quantize,
                            sampled_threshold_above=args.sampled_above)
    # full widths only fit with rematerialized layers (a full-width
    # mamba2-780m step needs ~77 GB without remat, ~13 GB with)
    bundle = build_train_step(cfg, mesh, ex_cfg, lr=args.lr,
                              batch_specs_abstract=input_specs(cfg, shape),
                              remat=not args.smoke)
    return cfg, mesh, bundle


def train(args, *, log=None, built=None, step=None) -> list[float]:
    """Run ``args.steps`` steps of the mesh train step on ``TokenStream``
    data; returns the per-step losses.  ``built`` is :func:`build`'s
    result and ``step`` an executable compiled from its bundle, where the
    caller made them ahead of time.  The caller has already started JAX
    on the devices it wants."""
    import jax

    from repro.checkpoint import save_checkpoint
    from repro.data.synthetic import TokenStream
    from repro.launch.steps import zeros_state
    from repro.models import init_params

    log = log or (lambda msg: None)
    cfg, mesh, bundle = built or build(args)
    log(f"[train] arch={cfg.name} smoke={args.smoke} "
        f"mesh={dict(mesh.shape)} mode={args.mode} density={args.density} "
        f"engine={args.engine} quantize={args.quantize}")
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex_state = zeros_state(bundle)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=0)
    losses = []
    with mesh:
        step = step or bundle.jit()
        for i in range(args.steps):
            batch = stream.batch(i)
            if cfg.frontend_tokens:
                batch["frontend_embeds"] = jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(1), i),
                    (args.batch, cfg.frontend_tokens, cfg.d_model),
                    cfg.cdtype)
            params, ex_state, loss = step(params, ex_state, batch)
            losses.append(float(loss))
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                log(f"  step {i:4d} loss={losses[-1]:.4f}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, step=args.steps)
        log(f"[train] saved {args.checkpoint}")
    return losses


def main():
    args = parse_args()

    from repro import telemetry

    log = telemetry.get_logger("train")
    if args.log_level:
        telemetry.set_level(args.log_level)
    if args.log_file:
        telemetry.set_log_file(args.log_file)

    force_host_devices(args.devices)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    train(args, log=log.info)
    log.info("[train] done")


if __name__ == "__main__":
    main()

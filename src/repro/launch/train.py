"""LLM training launcher (runs on the actually-present devices).

Example (reduced config, CPU):
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --reduced \
      --steps 50 --batch 8 --seq 128

On a real slice this is the same entry point with --no-reduced and the
production mesh; the dry-run (launch/dryrun.py) proves those programs
compile for 16x16 and 2x16x16.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.configs import get_config, reduced_config
from repro.data import SyntheticLM
from repro.models import transformer as T
from repro.config import ExecConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.checkpoint import save_checkpoint
from repro.sharding.rules import param_shardings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "pallas", "interpret", "ref",
                             "mosaic", "triton"],
                    help="kernel-backend request (REPRO_KERNEL_BACKEND "
                         "env var overrides)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    ec = ExecConfig(remat=args.remat, use_pallas=args.use_pallas,
                    kernel_backend=args.kernel_backend,
                    compute_dtype="float32" if args.reduced else "bfloat16")
    tc = TrainConfig(learning_rate=args.lr, warmup_steps=10, remat=args.remat)

    mesh = make_host_mesh()
    data = SyntheticLM(cfg.vocab, args.seq, args.batch)
    step_fn, opt = make_train_step(cfg, ec, tc)

    with jax.set_mesh(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0), ec)
        opt_state = opt.init(params)
        pshard = param_shardings(cfg, mesh, ec)
        del pshard  # host mesh is 1-way model; placement is trivial
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

        t0 = time.time()
        for i in range(args.steps):
            batch = data.batch(jnp.int32(i))
            if cfg.has_cross_attention:
                B = args.batch
                M = cfg.cross_memory_len
                batch = dict(batch, memory=jax.random.normal(
                    jax.random.PRNGKey(i), (B, M, cfg.d_model)) * 0.02)
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if (i + 1) % args.log_every == 0 or i == 0:
                print(f"step {i+1:4d} loss {float(metrics['loss']):.4f} "
                      f"ce {float(metrics['ce']):.4f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
        if args.ckpt_dir:
            path = save_checkpoint(args.ckpt_dir, args.steps,
                                   {"params": params})
            print("checkpoint:", path)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

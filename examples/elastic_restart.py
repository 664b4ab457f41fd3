"""Fault-tolerance demo: training with injected node failures — every
failure restores the latest committed checkpoint, re-partitions the data
stream for the surviving capacity (elastic), and continues.

    pip install -e . && python examples/elastic_restart.py
"""
import argparse

import jax
import jax.numpy as jnp

from repro.api import Session
from repro.runtime import enable_compile_cache
from repro.checkpoint import AsyncCheckpointer, latest_step, load_checkpoint
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMData
from repro.launch.train import AdamWConfig, TrainConfig, make_train_step
from repro.models import init_params
from repro.optim import adamw_init
from repro.runtime import ElasticScaler, run_with_restarts


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[7, 15])
    ap.add_argument("--ckpt-dir", default="/tmp/cello_elastic_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("granite-3-8b").reduced()
    plan = Session(cfg).default_plan(seq=32).plan
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=4, total_steps=args.steps)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      global_batch=8, seed=0))
    step_fn = jax.jit(make_train_step(cfg, plan, opt_cfg,
                                      TrainConfig(donate=False)))
    ck = AsyncCheckpointer(args.ckpt_dir, keep=3)
    scaler = ElasticScaler(model_axis=16, pod_chips=256)
    state = {"params": params, "opt": opt_state}
    fleet = {"devices": 768}           # three pods; each failure drops one
    to_fail = set(args.fail_at)

    def train_one(step: int) -> None:
        if step in to_fail:
            to_fail.discard(step)
            fleet["devices"] -= 256            # a whole pod drops out
            raise RuntimeError(f"pod failure at step {step}")
        x, y = data.batch_at(step)
        batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
        state["params"], state["opt"], m = step_fn(state["params"],
                                                   state["opt"], batch)
        print(f"  step {step:3d}  loss {float(m['loss']):.4f}  "
              f"devices={fleet['devices']}")
        if (step + 1) % 4 == 0:
            ck.save(step + 1, state, extra={"step": step + 1})
            ck.wait()

    def restore(failed_step: int) -> int:
        last = latest_step(args.ckpt_dir) or 0
        plan_ = scaler.plan(fleet["devices"], restore_step=last)
        print(f"  !! restoring step {last} onto mesh {plan_.mesh_shape} "
              f"({plan_.n_devices} chips)")
        if last > 0:
            restored, _ = load_checkpoint(args.ckpt_dir, last, state)
            state.update(restored)
        # elastic data repartition (single host here: shard 0 of 1)
        return last

    stats = run_with_restarts(train_one, restore, n_steps=args.steps,
                              max_restarts=5)
    ck.wait()
    print(f"\ncompleted {stats['completed']} steps with "
          f"{stats['restarts']} restarts; final capacity "
          f"{fleet['devices']} chips")


if __name__ == "__main__":
    main()

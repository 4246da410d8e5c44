"""Train a ~100M-param cloudlet LM for a few hundred steps (end-to-end
driver: data pipeline -> sharded train step -> checkpoints -> resume).

    PYTHONPATH=src python examples/train_cloudlet.py [--steps 300]

Uses a 100M-scale OLMo-family config on the synthetic Markov-chain token
stream; the loss should fall from ln(V) toward the stream's conditional
entropy.  Checkpoints land in ./checkpoints_example; rerunning resumes.
"""

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.data.lm_data import LMStreamSpec, conditional_entropy, token_stream
from repro.models.api import ModelAPI
from repro.train import optimizer as opt_lib
from repro.train.checkpoint import CheckpointManager
from repro.train.trainer import (PrefetchIterator, TrainLoop, TrainState,
                                 make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--small", action="store_true",
                    help="use the smoke config instead of ~100M")
    args = ap.parse_args()

    base = get_config("olmo-1b")
    if args.small:
        cfg = base.reduced()
        batch, seq = 8, 64
    else:
        # ~100M params: 8L x 768 wide OLMo-family, fp32 on CPU
        cfg = dataclasses.replace(
            base, name="olmo-100m", num_layers=8, d_model=768, num_heads=12,
            num_kv_heads=12, head_dim=64, d_ff=3072, vocab_size=8192,
            dtype_name="float32", remat="none")
        batch, seq = 8, 128
    api = ModelAPI(cfg)
    print(f"arch {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    params, _ = api.init(jax.random.PRNGKey(0))
    spec = opt_lib.OptimizerSpec(name="adamw", lr=3e-3)
    step_fn = jax.jit(make_train_step(
        api.loss, spec, opt_lib.cosine_schedule(3e-3, 20, args.steps)))

    stream = LMStreamSpec(vocab_size=cfg.vocab_size, batch=batch,
                          seq_len=seq, seed=0)
    print(f"synthetic-stream loss floor ~{conditional_entropy(stream):.3f} "
          f"nats (ln V = {float(jax.numpy.log(cfg.vocab_size)):.3f})")
    mgr = CheckpointManager("checkpoints_example", keep=2)
    loop = TrainLoop(step_fn, mgr, ckpt_every=100, log_every=20)
    state, hist = loop.run(TrainState.create(params, spec),
                           PrefetchIterator(token_stream(stream), depth=2),
                           num_steps=args.steps)
    print(f"done at step {int(state.step)}; "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Serving example: batched prefill + decode with KV caches / recurrent
state, across architecture families — the serve_step the decode dry-runs
lower, at CPU scale.

    PYTHONPATH=src python examples/serve_decode.py [--arch rwkv6-7b]
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.configs.base import reduced  # noqa: E402
from repro.models import build_model  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-7b", choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window decode (0 = full cache)")
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    model = build_model(cfg, decode_window=args.window)
    params = model.init(jax.random.key(0))
    total = args.prompt_len + args.gen_len
    state = model.init_decode(args.batch, total)

    key = jax.random.key(1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    if cfg.family == "audio":
        frames = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        state = model.precompute_cross(params, {"frame_embeds": frames},
                                       state)

    step = jax.jit(model.decode_step)
    # prefill token-by-token through the decode path (cache-filling);
    # greedy decode afterwards
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, state = step(params, state, prompt[:, t:t + 1])
    toks = [jnp.argmax(logits[:, 0, :cfg.vocab_size], -1)[:, None]]
    for _ in range(args.gen_len):
        logits, state = step(params, state, toks[-1])
        toks.append(jnp.argmax(logits[:, 0, :cfg.vocab_size], -1)[:, None])
    dt = time.time() - t0
    out = jnp.concatenate(toks, axis=1)
    print(f"arch={cfg.name} family={cfg.family} window={args.window}")
    print(f"decoded {args.gen_len} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.batch * (total) / dt:.1f} tok/s incl. prefill)")
    print("generated ids[0]:", out[0].tolist())


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Batched serving driver — the paper's inference story ("after training, a
client's private model can be used for inference") at LLM scale.

Implements a simple static-batch serving loop: prefill the prompt batch
into the KV/SSM cache, then step the decode loop token by token with greedy
or temperature sampling. On CPU this serves the reduced (smoke) variant.

Example::

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..configs.registry import smoke_variant
from ..nn.model import init_cache, init_model
from .steps import StepOptions, make_decode_step, make_prefill_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    key = jax.random.PRNGKey(args.seed)
    key, k_init, k_prompt, k_img, k_first = jax.random.split(key, 5)
    max_len = args.prompt_len + args.gen + (
        cfg.n_image_tokens if cfg.modality == "vlm" else 0)
    opts = StepOptions(remat=False, kv_chunk=max(64, args.prompt_len))

    params = init_model(k_init, cfg)
    cache = init_cache(cfg, args.batch, max_len, dtype=jnp.dtype(cfg.dtype))
    state = {"params": params, "cache": cache}

    tok_shape = ((args.batch, args.prompt_len, cfg.n_codebooks)
                 if cfg.modality == "audio" else (args.batch, args.prompt_len))
    prompt = jax.random.randint(k_prompt, tok_shape, 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.modality == "vlm":
        batch["img"] = jax.random.normal(
            k_img, (args.batch, cfg.n_image_tokens, cfg.frontend_dim),
            jnp.dtype(cfg.dtype))

    prefill = jax.jit(make_prefill_step(cfg, opts))
    decode = jax.jit(make_decode_step(cfg, opts))

    t0 = time.time()
    state, logits = prefill(state, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"[serve] {cfg.name}: prefill B={args.batch} S={args.prompt_len} "
          f"in {t_prefill:.2f}s")

    def sample(k, lg):
        if args.temperature <= 0:
            return jnp.argmax(lg, axis=-1)
        return jax.random.categorical(k, lg / args.temperature, axis=-1)

    pos0 = args.prompt_len + (cfg.n_image_tokens if cfg.modality == "vlm" else 0)
    tok = sample(k_first, logits)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        key, kk = jax.random.split(key)
        t_in = tok[:, None, :] if cfg.modality == "audio" else tok[:, None]
        state, logits = decode(state, {"tokens": t_in.astype(jnp.int32),
                                       "pos": jnp.asarray(pos0 + i, jnp.int32)})
        tok = sample(kk, logits)
        out.append(tok)
    jax.block_until_ready(out[-1])
    dt = time.time() - t0
    n_tok = args.batch * (args.gen - 1)
    print(f"[serve] decoded {args.gen-1} steps x batch {args.batch}: "
          f"{dt:.2f}s  ({n_tok/max(dt,1e-9):.1f} tok/s on CPU)")
    toks = jnp.stack(out, axis=1)
    print(f"[serve] sample tokens (client-private model output): "
          f"{toks[0].reshape(-1)[:16].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

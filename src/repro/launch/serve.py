"""Batched serving driver: prefill a batch of prompts, then decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1-5-0-5b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Continuous-batching style: the decode loop runs a fixed-shape step (one token
for the whole batch); finished sequences keep decoding into padding (masked
in the returned text), so the compiled step is reused for every token — the
TPU-friendly serving discipline.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_config, get_smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import lowering_rules
from repro.models.module import split_params
from repro.models.registry import build_model
from repro.sharding.partition import sharding_rules


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    compile_cache.enable()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    model = build_model(cfg)
    cache_len = args.prompt_len + args.gen
    shape_cfg = ShapeConfig("serve", cache_len, args.batch, "decode")
    mesh = make_host_mesh(data=jax.device_count(), model=1)
    rules = lowering_rules(cfg, shape_cfg, mesh)

    with mesh, sharding_rules(mesh, rules):
        params, _ = split_params(model.init(jax.random.key(args.seed)))
        rng = np.random.default_rng(args.seed)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32)}
        if cfg.family == "encdec":
            enc_len = model.enc_len(args.prompt_len)
            batch["frames"] = jnp.asarray(
                rng.standard_normal((args.batch, enc_len, cfg.d_model)),
                cfg.param_dtype)

        prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len))
        # donate the KV caches: decode_step(params, tok, caches, pos)
        # updates them in place instead of reallocating every token
        decode = jax.jit(model.decode_step, donate_argnums=(2,))

        t0 = time.time()
        logits, caches = prefill(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t_prefill = time.time() - t0

        out = [tok]
        t1 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            logits, caches = decode(params, tok, caches, pos)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t1

        gen = np.stack([np.asarray(t) for t in out], axis=1)
        print(f"prefill: {args.batch}x{args.prompt_len} tok "
              f"in {t_prefill * 1e3:.1f}ms")
        print(f"decode: {args.gen - 1} steps x {args.batch} seqs in "
              f"{t_decode * 1e3:.1f}ms "
              f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
        print("generated ids[0]:", gen[0].tolist())


if __name__ == "__main__":
    main()

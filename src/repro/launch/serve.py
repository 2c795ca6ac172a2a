"""Serving launcher: batched prefill + decode loop.

``python -m repro.launch.serve --arch smollm-135m --smoke --tokens 16``
prefills a batch of prompts and decodes N tokens per sequence, reporting
per-token latency. On a fleet the same entrypoint serves the full config on
the TP mesh (params bf16, TP-only shardings — see launch/dryrun.py).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config, get_smoke_config
from repro.models import build_model
from repro.runtime import DeltaSubscriber, DirTransport
from repro.sharding import mesh_context
from repro.train import make_decode_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--sync-spool", default=None, metavar="DIR",
                    help="subscribe to a trainer's delta spool "
                         "(train.py --publish-deltas DIR): fold parameter "
                         "deltas into live params between decode steps")
    ap.add_argument("--max-staleness", type=int, default=4,
                    help="hard staleness bound (epochs) before the replica "
                         "degrades to a shadow-checkpoint reload")
    ap.add_argument("--sync-every-tokens", type=int, default=1,
                    help="run one sync round every N decoded tokens")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    from repro.compat import make_mesh
    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))

    with mesh_context(mesh):
        params = model.init(jax.random.PRNGKey(0))
        B, S = args.batch, args.prompt_len
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
        kw = {}
        if cfg.family == "encdec":
            kw["embeds"] = jnp.zeros((B, cfg.n_frames, cfg.d_model), cfg.cdtype)
        if cfg.family == "vlm":
            kw["embeds"] = jnp.zeros((B, S, cfg.d_model), cfg.cdtype)

        t0 = time.perf_counter()
        if cfg.family == "vlm":
            logits, caches = model.prefill(params, embeds=kw["embeds"],
                                           max_len=S + args.tokens,
                                           attn_chunk=32)
        else:
            logits, caches = model.prefill(params, tokens=toks,
                                           max_len=S + args.tokens,
                                           attn_chunk=32, **kw)
        jax.block_until_ready(logits)
        print(f"prefill {B}x{S}: {(time.perf_counter()-t0)*1e3:.1f} ms")

        subscriber = None
        if args.sync_spool:
            subscriber = DeltaSubscriber(
                params, DirTransport(args.sync_spool),
                max_staleness=args.max_staleness,
                ckpt_dir=os.path.join(args.sync_spool, "ckpt"))

        decode = jax.jit(make_decode_step(model, attn_chunk=128))
        tok = jnp.argmax(logits, -1)
        outs = [tok]
        plain_lat, swap_lat = [], []
        t0 = time.perf_counter()
        for i in range(args.tokens - 1):
            t_tok = time.perf_counter()
            swapped = False
            if subscriber is not None and i % args.sync_every_tokens == 0:
                report = subscriber.sync()
                if report.window or report.degraded:
                    params = subscriber.params  # hot-swap between tokens
                    swapped = True
            logits, caches = decode(params, caches, tok)
            tok = jnp.argmax(logits, -1)
            outs.append(tok)
            if subscriber is not None:
                # per-token blocking so hot-swap jitter is measurable
                jax.block_until_ready(tok)
                lat = (time.perf_counter() - t_tok) * 1e3
                (swap_lat if swapped else plain_lat).append(lat)
                obs.histogram("delta_sync.decode_latency_ms").observe(lat)
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0
        per_tok = dt / max(1, args.tokens - 1) * 1e3
        print(f"decoded {args.tokens} tokens/seq: {per_tok:.1f} ms/token "
              f"({B / (per_tok / 1e3):.1f} tok/s aggregate)")
        print("sample token ids:", [int(t[0]) for t in outs][:10])
        if subscriber is not None:
            med = sorted(plain_lat)[len(plain_lat) // 2] if plain_lat else 0.0
            swp = max(swap_lat) if swap_lat else 0.0
            print(f"delta-sync: applied_epoch={subscriber.applied_epoch} "
                  f"degradations={subscriber.degradations} "
                  f"retries={subscriber.total_retries}; decode latency "
                  f"median {med:.1f} ms, worst hot-swap token {swp:.1f} ms",
                  flush=True)


if __name__ == "__main__":
    main()

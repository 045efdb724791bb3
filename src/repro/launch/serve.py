"""Batched serving driver: continuous prefill+decode over a request queue.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
        --requests 8 --prompt-len 64 --gen 32

Static-batch synchronous decode (all slots advance one position per step —
the configuration the decode_* dry-run cells lower).  Requests are packed
into fixed slots; finished slots are refilled from the queue (continuous
batching at slot granularity).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, ARCH_IDS
from repro.models import registry as R
from repro.launch.steps import make_prefill_step, make_serve_step


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    generated: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_done: Optional[float] = None


@partial(jax.jit, static_argnums=1)
def _init_params(key, cfg):
    return R.init_params(key, cfg)[0]


def make_params(cfg, seed: int = 0):
    """The model's random weights from ``seed``, built in one compiled
    program so the device holds no full-size temporary per weight."""
    return _init_params(jax.random.key(seed), cfg)


def serve(cfg, requests: List[Request], *, slots: int = 4,
          ctx_len: int = 512, seed: int = 0, greedy: bool = True):
    params = make_params(cfg, seed)
    prefill = jax.jit(make_prefill_step(cfg, cache_len=ctx_len))
    decode = jax.jit(make_serve_step(cfg, greedy=greedy))

    queue = list(requests)
    active: List[Optional[Request]] = [None] * slots
    done: List[Request] = []

    # NOTE (deliberate simplification, documented): synchronous decode means
    # one shared position counter; each admitted batch prefetches together.
    while queue or any(active):
        # admit a fresh batch into empty slots (batched prefill)
        if all(a is None for a in active) and queue:
            batch = [queue.pop(0) for _ in range(min(slots, len(queue)))]
            plen = max(len(r.prompt) for r in batch)
            toks = np.zeros((len(batch), plen), np.int32)
            for i, r in enumerate(batch):
                toks[i, -len(r.prompt):] = r.prompt      # left-pad
            logits, cache = prefill(params, {"tokens": jnp.asarray(toks)})
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            pos = plen
            for i, r in enumerate(batch):
                r.generated.append(int(nxt[i, 0]))
                active[i] = r
            # decode until every slot hits its budget
            while any(a is not None for a in active):
                nxt, logits, cache = decode(params, nxt, jnp.int32(pos),
                                            cache)
                pos += 1
                for i, r in enumerate(active):
                    if r is None:
                        continue
                    if len(r.generated) >= r.max_new:
                        r.t_done = time.time()
                        done.append(r)
                        active[i] = None
                    else:
                        r.generated.append(int(nxt[i, 0]))
            # free this batch's KV cache before the next prefill allocates
            del logits, cache
    return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="minicpm-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    reqs = [Request(i, rng.integers(1, cfg.vocab_size,
                                    size=args.prompt_len).astype(np.int32),
                    args.gen, t_submit=t0)
            for i in range(args.requests)]
    done = serve(cfg, reqs, slots=args.slots,
                 ctx_len=args.prompt_len + args.gen, seed=args.seed)
    wall = time.time() - t0
    n_tok = sum(len(r.generated) for r in done)
    print(f"[serve] arch={cfg.name} requests={len(done)} "
          f"new_tokens={n_tok} wall={wall:.2f}s "
          f"tok/s={n_tok / max(wall, 1e-9):.1f}")
    for r in done[:3]:
        print(f"  req{r.rid}: {r.generated[:10]}...")
    return done


if __name__ == "__main__":
    main()

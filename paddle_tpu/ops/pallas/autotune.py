"""Flash-attention block-size autotuner.

The right Pallas tile depends on (seq_len, head_dim, dtype, causal) —
the round-5 microbench measured blk=512 at 2-4x FASTER than the old
blk=128 default at seq 512/1024/2048, so a one-size tile keeps losing
(cf. the tile-tuning framing of arXiv:2301.13062 / arXiv:1811.05213).
This module makes the choice measured, cached, and shared:

  * `resolve(t, d, dtype, causal)` is consulted by
    `flash_attention` whenever the caller leaves block_q/block_k unset.
    It answers from a process-global memo, then from a persistent JSON
    cache, and — only under `FLAGS_flash_autotune=full` on a real TPU —
    by timing a small candidate grid ({128, 256, 512}, divisor-clamped
    via `_pick_block`) on the device and memoizing the winner.
  * `FLAGS_flash_autotune=cached` (the default) never tunes: a miss
    simply falls back to `FLAGS_flash_attention_block_{q,k}`, so CPU
    tier-1 runs pay one dict lookup and nothing else. `off` disables
    even the lookup.
  * The JSON cache (`FLAGS_flash_autotune_cache`, default in the
    compilation-cache directory of core/compile_cache.py) can be seeded from real chip time by
    `tools/attn_micro.py --emit-cache`, so one microbench run tunes
    every later process.

Monitor wiring: `flash.autotune_cache_hit` / `flash.autotune_cache_miss`
counters and a `flash.autotune_sweep_seconds` histogram (names in
docs/observability.md).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

from ...monitor import STAT_ADD, STAT_OBSERVE

CACHE_VERSION = 1

# candidate q=k tiles; each is divisor-clamped to the padded sequence
# via flash_attention._pick_block before timing, so the swept set is
# always TPU-legal and duplicates collapse
CANDIDATE_BLOCKS = (128, 256, 512)

_LOCK = threading.Lock()
# (t, d, dtype, causal) -> (block_q, block_k); process-global so every
# executor/program in the process shares one tuning result
_MEMO: Dict[tuple, Tuple[int, int]] = {}
# persistent-cache entries, loaded at most once per (process, path)
_FILE_ENTRIES: Optional[Dict[str, dict]] = None
_FILE_PATH_LOADED: Optional[str] = None


def cache_key(t: int, d: int, dtype, causal: bool) -> str:
    """Stable string key for the JSON cache: padded seq, head_dim,
    canonical dtype name, causal bit."""
    return f"t{int(t)}_d{int(d)}_{str(dtype)}_c{int(bool(causal))}"


def default_cache_path() -> str:
    """FLAGS_flash_autotune_cache, or a file in the compilation-cache
    directory (core/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache)."""
    from ...core.compile_cache import compile_cache_dir
    from ...core.flags import FLAGS
    if FLAGS.flash_autotune_cache:
        return FLAGS.flash_autotune_cache
    return os.path.join(compile_cache_dir(), "flash_autotune.json")


def load_cache(path: Optional[str] = None) -> Dict[str, dict]:
    """Entries of the persistent cache ({} when absent/corrupt)."""
    path = path or default_cache_path()
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("version") != CACHE_VERSION:
            return {}
        entries = doc.get("entries", {})
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def store(entries: Dict[str, dict], path: Optional[str] = None,
          source: str = "autotune") -> str:
    """Merge `entries` ({cache_key: {"block_q": int, "block_k": int,
    ...}}) into the persistent cache (atomic rewrite) and invalidate the
    in-process copy so the next resolve() sees them. Returns the path."""
    path = path or default_cache_path()
    merged = load_cache(path)
    for k, v in entries.items():
        rec = dict(v)
        rec.setdefault("source", source)
        merged[k] = rec
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": merged}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)
    global _FILE_ENTRIES, _FILE_PATH_LOADED
    with _LOCK:
        _FILE_ENTRIES = None
        _FILE_PATH_LOADED = None
    return path


def reset_memo():
    """Drop the process-global memo + loaded file cache (tests)."""
    global _FILE_ENTRIES, _FILE_PATH_LOADED
    with _LOCK:
        _MEMO.clear()
        _FILE_ENTRIES = None
        _FILE_PATH_LOADED = None


def _file_lookup(key: str) -> Optional[Tuple[int, int]]:
    """Lazy-loaded persistent-cache lookup (one file read per process,
    re-read only after store())."""
    global _FILE_ENTRIES, _FILE_PATH_LOADED
    path = default_cache_path()
    with _LOCK:
        if _FILE_ENTRIES is None or _FILE_PATH_LOADED != path:
            _FILE_ENTRIES = load_cache(path)
            _FILE_PATH_LOADED = path
        rec = _FILE_ENTRIES.get(key)
    if not rec:
        return None
    try:
        return int(rec["block_q"]), int(rec["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def _on_device() -> bool:
    """True only when the tiled kernel would actually run on hardware —
    interpret mode / CPU short-circuits the tuning sweep (tier-1 runs
    must never pay it)."""
    from .flash_attention import _interpret
    return not _interpret()


def _sweep(t: int, d: int, dtype, causal: bool,
           iters: int = 5) -> Tuple[int, int]:
    """Time the candidate grid (fwd+bwd, q=k tiles) on the real device
    and return the winner. A candidate that does not compile or run
    raises: every candidate is a tile the flags may select, so a kernel
    failure here is a kernel failure, not a reason to pick another."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import _pick_block, flash_attention

    candidates = sorted({_pick_block(t, c) for c in CANDIDATE_BLOCKS})
    if len(candidates) == 1:
        return candidates[0], candidates[0]
    key = jax.random.PRNGKey(0)
    bh = 8
    q = jax.random.normal(key, (bh, t, d), jnp.dtype(dtype))
    k = jax.random.normal(key, (bh, t, d), jnp.dtype(dtype))
    v = jax.random.normal(key, (bh, t, d), jnp.dtype(dtype))
    best, best_dt = None, None
    for blk in candidates:
        def loss(q_, k_, v_, _blk=blk):
            return jnp.sum(flash_attention(
                q_, k_, v_, causal=causal, block_q=_blk,
                block_k=_blk).astype(jnp.float32))

        g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        out = g(q, k, v)
        jax.block_until_ready(out)   # compile outside the window
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        if best_dt is None or dt < best_dt:
            best, best_dt = blk, dt
    return best, best


def resolve(t: int, d: int, dtype, causal: bool) \
        -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for a flash op whose caller left the blocks
    unset, or None when the flag defaults should govern.

    Order: process memo -> persistent JSON cache -> (full mode, real
    TPU only) timing sweep. `off` skips everything; `cached` (default)
    never tunes, so a miss costs one dict lookup."""
    from ...core.flags import FLAGS
    mode = FLAGS.flash_autotune
    if mode not in ("off", "cached", "full"):
        raise ValueError(
            f"FLAGS_flash_autotune={mode!r}: expected off|cached|full")
    if mode == "off":
        return None
    memo_key = (int(t), int(d), str(dtype), bool(causal))
    with _LOCK:
        hit = _MEMO.get(memo_key)
    if hit is not None:
        STAT_ADD("flash.autotune_cache_hit")
        return hit
    fkey = cache_key(t, d, dtype, causal)
    hit = _file_lookup(fkey)
    if hit is not None:
        STAT_ADD("flash.autotune_cache_hit")
        with _LOCK:
            _MEMO[memo_key] = hit
        return hit
    STAT_ADD("flash.autotune_cache_miss")
    if mode != "full" or not _on_device():
        return None
    t0 = time.perf_counter()
    tuned = _sweep(t, d, dtype, causal)
    STAT_OBSERVE("flash.autotune_sweep_seconds",
                 time.perf_counter() - t0)
    with _LOCK:
        _MEMO[memo_key] = tuned
    try:
        store({fkey: {"block_q": tuned[0], "block_k": tuned[1]}},
              source="autotune")
    except OSError:
        pass  # unwritable cache dir must not lose the in-process win
    return tuned

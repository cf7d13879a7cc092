"""Shape-bucket block-size autotuning for the Pallas kernel ops.

The kernel wrappers in :mod:`repro.kernels.ops` used to hard-code one block
configuration (``block_n=8, block_l=256``, ...) for every shape they were
launched at. The right tiling depends on the launch shape — how much of L
fits a VMEM tile, how many frontier rows amortize a grid step — so this
module keeps a small table:

    (op, shape bucket) -> {block_*: int, ...}

* **Buckets**, not exact shapes: every dimension is rounded up to its next
  power of two, so one timed entry covers the whole family of shapes the
  serving engine's static buckets generate.
* **Resolution order** (``repro.kernels.ops._resolve``): an explicit block
  argument wins, then a tuned table entry, then the per-op default below.
  Resolution happens at Python trace time — block sizes are static to the
  compiled executable, so retuning never invalidates a warm cache (the
  engine autotunes BEFORE it AOT-compiles its buckets).
* **Persistence**: :func:`save_table` / :func:`load_table` round-trip the
  table through JSON so CI lanes and serving replicas reuse one tuned
  table instead of re-timing at every warmup
  (``EngineConfig.tuning_table``).

:func:`autotune` itself is measurement-only plumbing — it times a caller
supplied runner over :func:`candidates` and records the winner. The
runners that build synthetic arrays for each op live in
``repro.kernels.ops.autotune_op`` (ops imports this module, not the other
way around).
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

# Per-op fallback block configuration — the values the ops shipped with
# before tuning existed, except maxsim's block_t: the old ``block_t=0``
# resolved to the FULL query-token axis, which blows the documented VMEM
# tile budget for unbucketed large-T calls; 128 is the one-lane-tile cap
# the kernel's tile math is written for (kernels/maxsim.py header). The
# batched op shares that cap: a narrower BT than the bucketed T would make
# its (BN, BT) output block illegal on the chip.
DEFAULTS: Dict[str, Dict[str, int]] = {
    "maxsim": {"block_n": 8, "block_t": 128, "block_l": 256},
    "maxsim_batch": {"block_n": 8, "block_t": 128, "block_l": 128},
    "masked_maxsim": {"block_l": 256},
    "gather_maxsim": {"block_b": 8, "block_l": 256},
    "fused_reveal": {"block_b": 8, "block_l": 256},
}

# Candidate grids per op — small but non-degenerate: autotuning compiles
# one executable per candidate, and warmup budgets are real. Candidates
# whose block exceeds the (padded) dimension collapse to the clamped
# config, so duplicates are pruned against the launch dims before timing.
# Every candidate obeys Mosaic's block rule (last two block dims divide by
# (8, 128) or equal the array's): the token-mask blocks put BL on lanes,
# so BL is a multiple of 128, and BT stays at the 128 cap, which clamps to
# the whole (bucketed) T axis. The same grids serve the quantized
# (int8/residual) launches — those buckets carry an FMT dim (see
# ops._fmt_dims), so each format records its own winner per shape.
_ROW_TILES = [{"block_n": n, "block_t": 128, "block_l": l}
              for n in (8, 16, 32) for l in (256, 128)]
CANDIDATES: Dict[str, List[Dict[str, int]]] = {
    "maxsim": _ROW_TILES,
    "maxsim_batch": _ROW_TILES,
    "gather_maxsim": [
        {"block_b": 8, "block_l": 256},
        {"block_b": 16, "block_l": 128},
        {"block_b": 32, "block_l": 128},
        {"block_b": 8, "block_l": 128},
    ],
    "fused_reveal": [
        {"block_b": 8, "block_l": 256},
        {"block_b": 16, "block_l": 128},
        {"block_b": 8, "block_l": 128},
    ],
}

_TABLE: Dict[Tuple, Dict[str, int]] = {}


def _pow2_bucket(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def bucket_key(op: str, dims: Dict[str, int]) -> Tuple:
    """(op, ((dim, pow2-rounded size), ...)) — the table's lookup key."""
    return (op, tuple(sorted((k, _pow2_bucket(v)) for k, v in dims.items())))


def lookup(op: str, dims: Dict[str, int]) -> Dict[str, int]:
    """Tuned entry for the op at these dims, merged over its defaults."""
    cfg = dict(DEFAULTS.get(op, {}))
    cfg.update(_TABLE.get(bucket_key(op, dims), {}))
    return cfg


def record(op: str, dims: Dict[str, int], config: Dict[str, int]) -> None:
    _TABLE[bucket_key(op, dims)] = dict(config)


def table() -> Dict[Tuple, Dict[str, int]]:
    return dict(_TABLE)


def clear() -> None:
    _TABLE.clear()


def table_json(keys: Optional[set] = None) -> List[Dict[str, Any]]:
    """The table as JSON-ready rows (also what ``save_table`` writes).
    ``keys`` restricts to those bucket keys (the serving engine persists
    only its own buckets out of the process-shared cache)."""
    return [{"op": op, "bucket": dict(bucket), "config": dict(cfg)}
            for (op, bucket), cfg in sorted(_TABLE.items())
            if keys is None or (op, bucket) in keys]


def save_table(path: str, *, keys: Optional[set] = None) -> None:
    with open(path, "w") as f:
        json.dump(table_json(keys), f, indent=1)


def load_table(path: str) -> int:
    """Merge a persisted table into the live one; returns entries loaded."""
    with open(path) as f:
        rows = json.load(f)
    for row in rows:
        key = (row["op"], tuple(sorted(
            (k, int(v)) for k, v in row["bucket"].items())))
        _TABLE[key] = {k: int(v) for k, v in row["config"].items()}
    return len(rows)


def candidates(op: str, dims: Dict[str, int]) -> List[Dict[str, int]]:
    """The op's candidate grid, clamped to the launch dims and deduped.

    Clamping mirrors the ops' own ``min(block, dim)`` guard so two
    candidates that collapse to the same effective config are timed once.
    """
    clamp = {"block_n": dims.get("N"), "block_t": dims.get("T"),
             "block_l": dims.get("L"), "block_b": dims.get("B")}
    out: List[Dict[str, int]] = []
    for cand in CANDIDATES.get(op, [DEFAULTS.get(op, {})]):
        eff = {k: (min(v, clamp[k]) if clamp.get(k) else v)
               for k, v in cand.items()}
        if eff not in out:
            out.append(eff)
    return out


def time_call(fn: Callable[[], Any], *, repeats: int = 3) -> float:
    """Best-of-N wall time of ``fn`` (compile/warm excluded by a first
    untimed call). ``fn`` must block until its result is materialized."""
    fn()
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(op: str, dims: Dict[str, int],
             runner: Callable[..., Callable[[], Any]], *,
             repeats: int = 3,
             cands: Optional[Iterable[Dict[str, int]]] = None,
             ) -> Tuple[Dict[str, int], Dict[str, float]]:
    """Time ``runner(**candidate)`` over the candidate grid, record the
    winner for (op, dims), and return (best_config, per-candidate timings).

    ``runner`` is called once per candidate and must return a 0-arg
    callable executing the op at that block configuration (the runner owns
    argument construction so autotuning works against real serving arrays
    or synthetic ones alike).
    """
    timings: Dict[str, float] = {}
    best_cfg: Optional[Dict[str, int]] = None
    best_t = float("inf")
    for cand in (cands if cands is not None else candidates(op, dims)):
        t = time_call(runner(**cand), repeats=repeats)
        timings[json.dumps(cand, sort_keys=True)] = t
        if t < best_t:
            best_t, best_cfg = t, dict(cand)
    if best_cfg is None:
        raise ValueError(f"autotune({op!r}): empty candidate set")
    record(op, dims, best_cfg)
    return best_cfg, timings

"""Seeded weights, written fast in the program's artifact format.

``export_artifact`` initialises leaf by leaf on the host in float32 and took
26 s for a 3.26 GiB tenant (PERF.md, PR 21). Every run of every cell pays the
set-up, so the benchmark makes a tenant's weights on the device in ONE jitted
call from the seed, in the dtype they are served in, fetches them once and
streams them into one ``params.bin`` with the manifest ``load_artifact``
reads (``tpusc.v2``: leaves grouped by dtype, 16-byte aligned offsets,
``model.json`` written last).

The distribution is the program's own initialiser's (normal / sqrt(fan_in),
gains 1), so the logits have the scale the program's tests assume. Which
leaves a model has, and the pytree the program's loader expects, are its
family's (``families/<family>.py``: ``leaf_shapes``, ``to_tree``,
``param_bytes``, ``PROGRAM_FAMILY``); ``family`` below is that module and
``mc`` the program's config for it.
"""

from __future__ import annotations

import functools
import json
import math
import os
import time
from typing import Any

import numpy as np

ALIGN = 16
WRITE_CHUNK = 64 << 20


@functools.lru_cache(maxsize=4)
def _generator(family, mc_key: str):
    import jax
    import jax.numpy as jnp

    mc = json.loads(mc_key)
    shapes = family.leaf_shapes(mc)
    dtype = jnp.dtype(mc["dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {
            name: (jax.random.normal(k, shape, dtype)
                   * jnp.asarray(1.0 / math.sqrt(fan_in), dtype))
            for k, (name, (shape, fan_in)) in zip(keys, shapes.items())
        }

    return make


def make_on_device(family, mc: dict[str, Any], seed: int):
    """-> dict of stacked device arrays (async: returns before they exist)."""
    import jax

    return _generator(family, json.dumps(mc, sort_keys=True))(
        jax.random.PRNGKey(seed))


def _flat(tree: Any, prefix: str = ""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], f"{prefix}{k}/")
    elif isinstance(tree, list):
        for i, v in enumerate(tree):
            yield from _flat(v, f"{prefix}{i}/")
    else:
        yield prefix[:-1], tree


def write_artifact(dest: str, family, mc: dict[str, Any], tree: dict) -> int:
    """Write ``tree`` as a ``tpusc.v2`` artifact under ``dest`` -> bytes, in
    ``save_artifact``'s own layout. Small leaves are gathered into one staging
    buffer and large ones written in pieces of its size: one write a leaf
    cost 13 s for twelve 0.72 GB tenants of 290 leaves each, and one write of
    a whole 0.94 GB leaf ran at 1.3 GB/s where 64 MiB pieces ran at 3 GB/s
    (my chip runs, PR 22)."""
    from tfservingcache_tpu.models.registry import build

    model = build(family.PROGRAM_FAMILY, mc)
    os.makedirs(dest, exist_ok=True)
    leaves = sorted(enumerate(_flat(tree)),
                    key=lambda e: (e[1][1].dtype.name, e[0]))
    manifest, offset = [], 0
    stage, used = np.empty(WRITE_CHUNK, np.uint8), 0
    with open(os.path.join(dest, "params.bin"), "wb", buffering=0) as f:
        def flush() -> None:
            nonlocal used
            if used:
                f.write(memoryview(stage)[:used])
                used = 0

        for _, (path, a) in leaves:
            pad = (-offset) % ALIGN
            # bfloat16 has no buffer protocol: view the same bytes as uint8
            raw = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
            if used + pad + raw.nbytes > WRITE_CHUNK:
                flush()
            if pad:
                stage[used:used + pad] = 0
                used += pad
                offset += pad
            manifest.append({"path": path, "dtype": a.dtype.name,
                             "shape": list(a.shape), "offset": offset,
                             "nbytes": a.nbytes})
            if raw.nbytes > WRITE_CHUNK // 4:
                flush()
                for lo in range(0, raw.nbytes, WRITE_CHUNK):
                    f.write(memoryview(raw[lo:lo + WRITE_CHUNK]))
            else:
                stage[used:used + raw.nbytes] = raw
                used += raw.nbytes
            offset += raw.nbytes
        flush()
    meta = {
        "format": "tpusc.v2", "family": family.PROGRAM_FAMILY,
        "config": model.config, "param_dtype": model.store_param_dtype,
        "quantize": None,
        "params": {"file": "params.bin", "manifest": manifest},
        "signature": {
            "inputs": {k: [s.dtype, list(s.shape)]
                       for k, s in model.input_spec.items()},
            "outputs": {k: [s.dtype, list(s.shape)]
                        for k, s in model.output_spec.items()},
            "method_name": model.method_name,
        },
    }
    with open(os.path.join(dest, "model.json"), "w") as f:
        json.dump(meta, f)
    return offset


def write_tenants(store: str, names: list[str], family, mc: dict[str, Any],
                  seed: int, keep: int) -> tuple[list[dict], int, dict]:
    """Make and write one artifact per name (``<store>/<name>/1/``), the
    next tenant generating on the device while this one is written ->
    (the host trees of the first ``keep`` tenants, bytes written, the
    seconds spent making the first tenant, fetching and writing files)."""
    import jax

    kept: list[dict] = []
    total = 0
    split = {"make_s": 0.0, "fetch_s": 0.0, "file_s": 0.0}
    t = time.monotonic()
    pending = jax.block_until_ready(
        make_on_device(family, mc, seed * 1000 + 1))
    split["make_s"] = time.monotonic() - t
    for i, name in enumerate(names):
        stacked = pending
        if i + 1 < len(names):
            pending = make_on_device(family, mc, seed * 1000 + i + 2)
        t = time.monotonic()
        host = jax.device_get(stacked)
        del stacked
        split["fetch_s"] += time.monotonic() - t
        t = time.monotonic()
        tree = family.to_tree(mc, host)
        total += write_artifact(os.path.join(store, name, "1"), family, mc, tree)
        split["file_s"] += time.monotonic() - t
        if i < keep:
            kept.append(tree)
    return kept, total, split

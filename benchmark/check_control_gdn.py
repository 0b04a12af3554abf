"""``check_control_state.py`` for a cell whose program keeps a lane state
beside ONE global arena and no window ring (``olmo_hybrid_lm``: the linear
layers' matrix states and convolution tails, MHA pages for the full layers):
the two readings a cell's ``tolerance_std`` is set between, on the chip, at
the cell's own sizes. ``check_control_state.py`` admits through
``_window_paged_insert_jit`` and hands the decode chunk a ring, and is an
accepted benchmark file that a later PR may not edit; this file is that one
with the plain ``_paged_insert_jit`` and no ring, and nothing else changed.

For each seed: weights from the seed (``weights.py``), the engine's own
programs (``_slot_prefill_jit``, ``_paged_insert_jit``, ``_lane_insert_jit``,
``_paged_decode_chunk_jit``) at the configuration's lanes and pages, the
cell's check prompts in two lanes at once, ``checks.generate_new_tokens``
greedy tokens each; then ``reference.greedy_slack`` against the family's plain
reference (SOUND), and for the first ``--fp8`` seeds also against the
reference computed with its weight matrices rounded to float8 (e4m3), the
nearest precision below the bf16 the configuration states (FLOAT8: must read
over the limit).

    python benchmark/check_control_gdn.py --seeds 24 --fp8 4 [--first N]
        [--cell olmohybrid-longdoc-steady] [--rehearsal] [--no-kernel]
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import reference  # noqa: E402
import run as bench  # noqa: E402
import weights  # noqa: E402
from tfservingcache_tpu.models import generation as G  # noqa: E402
from tfservingcache_tpu.models.registry import build, static_config  # noqa: E402
from tfservingcache_tpu.utils import compile_cache  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--seeds", type=int, default=24)
ap.add_argument("--fp8", type=int, default=4)
ap.add_argument("--first", type=int, default=2147480100)
ap.add_argument("--cell", default="olmohybrid-longdoc-steady")
ap.add_argument("--rehearsal", action="store_true")
ap.add_argument("--no-kernel", action="store_true",
                help="decode through the gather + einsum references")
args = ap.parse_args()

compile_cache.configure()
_bench, cell, config = bench.load_cell(args.cell, args.rehearsal)
F = bench.load_family(config)
mc = F.program_config(config)
model = build(F.PROGRAM_FAMILY, mc)
cfg_key = static_config(model)
cfg = dict(cfg_key)
srv = config["server"]["serving"]
lanes, pt, pages = srv["generate_slots"], srv["kv_page_tokens"], srv["kv_arena_pages"]
pps = -(-mc["max_seq"] // pt)
pages = pages or lanes * pps
lens = cell["checks"]["generate_prompt_lens"]
n_new = cell["checks"]["generate_new_tokens"]
print("device", jax.devices()[0].device_kind, "lens", lens, "new", n_new, flush=True)


def bucket(n):
    b = 1
    while b < n:
        b *= 2
    return b


def program_tokens(params, prompts):
    cache = G.init_paged_cache(cfg, pages + 1, pt, row=model.cache_row, lanes=lanes)
    k, v = cache["k"], cache["v"]
    state = G.init_lane_state(cfg, lanes)
    tables = np.zeros((lanes, pps), np.int32)
    tok = np.zeros(lanes, np.int32)
    pos = np.zeros(lanes, np.int32)
    active = np.zeros(lanes, bool)
    out, free = [], 1
    for lane, prompt in enumerate(prompts):
        lane = (3 + 7 * lane) % lanes             # not the first lanes
        plen = len(prompt)
        ids = np.zeros((1, bucket(plen)), np.int32)
        ids[0, :plen] = prompt
        first, pk, pv, _last, lane_state = G._slot_prefill_jit(
            params, ids, np.asarray([plen], np.int32), jax.random.PRNGKey(1),
            np.float32(0), np.int32(0), cfg_key=cfg_key, family=F.PROGRAM_FAMILY)
        need = -(-(plen + n_new) // pt)           # the arena is shared: a
        tables[lane, :need] = free + np.arange(need)   # request's own pages
        free += need
        k, v, _ = G._paged_insert_jit(
            k, v, None, pk, pv, tables[lane], np.int32(0), page_tokens=pt)
        del pk, pv
        state = G._lane_insert_jit(state, lane_state, np.int32(lane))
        tok[lane], pos[lane], active[lane] = int(first[0]), plen, True
        out.append((lane, [int(first[0])]))
    assert free <= pages + 1, (free, pages)
    counter = np.uint32(1)
    tok_d, pos_d = jnp.asarray(tok), jnp.asarray(pos)
    for _ in range((n_new - 1 + 7) // 8):
        (k, v, _s, tok_d, pos_d, toks, _stats, state, counter
         ) = G._paged_decode_chunk_jit(
            params, k, v, None, tables, tok_d, pos_d, active, counter,
            np.zeros(lanes, np.float32), np.zeros(lanes, np.int32), state, None,
            cfg_key=cfg_key, family=F.PROGRAM_FAMILY, chunk=8, page_tokens=pt,
            kernel=not args.no_kernel)
        toks = np.asarray(toks)
        for lane, got in out:
            got.extend(int(t) for t in toks[lane])
    return [got[:n_new] for _lane, got in out]


def slack(tree, prompts, tokens):
    refs = F.logits_many(mc, tree, [list(p) + t[:-1] for p, t in zip(prompts, tokens)],
                         n_new)
    return [reference.greedy_slack(r, t) for r, t in zip(refs, tokens)]


def float8_in_place(host):
    """Every matrix of ``host`` rounded to float8 (e4m3) and back, one leaf at
    a time through the device: no second copy of the tenant."""
    for name, a in host.items():
        if a.ndim >= 2:
            host[name] = jax.device_get(
                jnp.asarray(a).astype(jnp.float8_e4m3fn).astype(a.dtype))


sound, low = [], []
for i in range(args.seeds):
    seed = args.first + i
    t0 = time.monotonic()
    stacked = jax.block_until_ready(weights.make_on_device(F, mc, seed * 1000 + 1))
    host = dict(jax.device_get(stacked))
    del stacked
    params = jax.device_put(F.to_tree(mc, host))
    rng = np.random.default_rng([seed, 0x3A])
    prompts = [rng.integers(1, mc["vocab_size"], n).tolist() for n in lens]
    tokens = program_tokens(params, prompts)
    del params
    program_peak = bench.memory_peak_bytes()     # before any reference ran
    got = slack(F.to_tree(mc, host), prompts, tokens)
    sound += got
    row = {"seed": seed, "sound": [round(x, 4) for x in got]}
    if i < args.fp8:
        float8_in_place(host)
        got8 = slack(F.to_tree(mc, host), prompts, tokens)
        low.append(got8)
        row["float8_reference"] = [round(x, 4) for x in got8]
    del host
    row["seconds"] = round(time.monotonic() - t0, 1)
    row["program_peak_device_bytes"] = program_peak
    row["peak_device_bytes"] = bench.memory_peak_bytes()
    print("SLACK " + json.dumps(row), flush=True)
print("SOUND largest", max(sound), "sorted top", sorted(sound)[-5:],
      "median", float(np.median(sound)), "n", len(sound))
if low:
    print("FLOAT8 per seed (max of its two sequences)", [max(r) for r in low],
          "lowest single", min(min(r) for r in low))

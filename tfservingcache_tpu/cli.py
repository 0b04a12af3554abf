"""``tpuserve`` CLI — entry point wiring (reference cmd/taskhandler/main.go:20-43).

Grows with the build: ``serve`` starts the cache node (and the proxy/router
when discovery is configured), ``export`` writes model artifacts.
"""

from __future__ import annotations

import argparse
import sys

from tfservingcache_tpu.config import load_config
from tfservingcache_tpu.utils.logging import get_logger, setup_logging

log = get_logger("cli")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="tpuserve", description=__doc__)
    parser.add_argument("--config", default=None, help="path to config.yaml")
    sub = parser.add_subparsers(dest="cmd", required=True)
    sub.add_parser("serve", help="run a cache node (+ proxy when discovery is configured)")
    exp = sub.add_parser("export", help="export a model artifact to a provider dir")
    exp.add_argument("model", help="model family name (see tfservingcache_tpu.models.registry)")
    exp.add_argument("dest", help="destination dir (<base>/<name>/<version> is created)")
    exp.add_argument("--name", default=None)
    exp.add_argument("--version", type=int, default=1)
    exp.add_argument(
        "--quantize", choices=["int8"], default=None,
        help="store large float weights as int8 + per-channel scales "
             "(device dequant at load; halves the cold-path transfer)",
    )
    exp.add_argument(
        "--config-json", default=None, metavar="JSON",
        help="family config overrides as a JSON object, e.g. "
             '\'{"d_model": 512, "n_layers": 8}\' (merged over the '
             "family's defaults)",
    )
    exp.add_argument("--seed", type=int, default=0,
                     help="parameter init seed")
    rep = sub.add_parser(
        "repack",
        help="rewrite an artifact in the current format (tpusc.v1 msgpack -> "
        "tpusc.v2 packed bin; applies the family's storage dtype)",
    )
    rep.add_argument("src", help="existing artifact dir (<...>/<name>/<version>)")
    rep.add_argument("dest", help="output artifact dir")
    wrm = sub.add_parser(
        "warm",
        help="pre-populate the persistent XLA compile cache "
        "(JAX_COMPILATION_CACHE_DIR, else serving.compile_cache_dir, else "
        "the in-checkout default) with an artifact's serving programs — "
        "bake into the deploy image so a node's FIRST cold load is a "
        "compile-cache hit (SURVEY §7: load-bearing for the <=2s target)",
    )
    wrm.add_argument("artifact", help="artifact dir (<...>/<name>/<version>)")
    wrm.add_argument(
        "--batches", default="1,2,4,8",
        help="comma-separated predict batch buckets to compile",
    )
    wrm.add_argument(
        "--lm-seq", type=int, default=128,
        help="prompt length for LM-family predict/generate programs",
    )
    wrm.add_argument(
        "--generate-tokens", type=int, default=32,
        help="decode program length for LM families (0 skips generate)",
    )
    args = parser.parse_args(argv)

    cfg = load_config(args.config)
    setup_logging(cfg.logging.level, cfg.logging.fmt)
    if cfg.serving.platform:
        # before any backend init (serve AND export both touch jax)
        import jax

        jax.config.update("jax_platforms", cfg.serving.platform)

    if args.cmd == "serve":
        from tfservingcache_tpu.server import run_server
        from tfservingcache_tpu.utils import compile_cache

        log.info("compile cache: %s",
                 compile_cache.configure(cfg.serving.compile_cache_dir))
        run_server(cfg)
        return 0
    if args.cmd == "export":
        import json as _json

        from tfservingcache_tpu.models.registry import export_artifact

        config = None
        if args.config_json is not None:
            # empty string falls through json.loads and fails loudly like
            # every other malformed value (a silently-ignored unset $CFG
            # would export defaults the user didn't ask for)
            try:
                config = _json.loads(args.config_json)
                if not isinstance(config, dict):
                    raise ValueError("must be a JSON object")
            except ValueError as e:
                log.error("invalid --config-json: %s", e)
                return 2
        path = export_artifact(args.model, args.dest, name=args.name,
                               version=args.version, seed=args.seed,
                               config=config, quantize=args.quantize)
        print(path)
        return 0
    if args.cmd == "repack":
        import json as _json
        import os as _os

        from tfservingcache_tpu.models.registry import load_artifact, save_artifact

        # carry the source's quantize marker AND bytes through: raw_quant
        # returns QuantLeaf views that save_artifact writes verbatim —
        # dequantize-then-requantize would shift scales and compound error
        # on every repack
        try:
            with open(_os.path.join(args.src, "model.json")) as f:
                src_quant = _json.load(f).get("quantize")
        except (OSError, ValueError):
            src_quant = None
        model, params = load_artifact(args.src, raw_quant=True)
        print(save_artifact(args.dest, model, params, quantize=src_quant))
        return 0
    if args.cmd == "warm":
        return _warm(cfg, args)
    return 2


def _warm(cfg, args) -> int:
    """Compile an artifact's serving programs through the REAL runtime (the
    persisted cache keys must match what `serve` will look up) and leave
    them in the persistent XLA compile cache."""
    import os
    import time

    import numpy as np

    from tfservingcache_tpu.cache.disk_cache import dir_size_bytes
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId
    from tfservingcache_tpu.utils import compile_cache

    cache_dir = compile_cache.configure(cfg.serving.compile_cache_dir)
    art = os.path.abspath(args.artifact)
    version_s = os.path.basename(art)
    name = os.path.basename(os.path.dirname(art))
    mid = ModelId(name or "model", int(version_s) if version_s.isdigit() else 1)
    rt = TPUModelRuntime(cfg.serving)
    compiled = []
    t0 = time.perf_counter()
    try:
        rt.ensure_loaded(Model(identifier=mid, path=art,
                               size_on_disk=dir_size_bytes(art)))
        in_spec, _, _ = rt.signature(mid)
        family = rt.family_of(mid)
        loaded = rt._resident.get(mid, touch=False)
        max_seq = int(loaded.model_def.config.get("max_seq", 0) or 0)
        seq = args.lm_seq
        gen_tokens = args.generate_tokens
        if max_seq:
            # clamp to what the model can serve: a default 128/32 against a
            # small max_seq must warm the usable shapes, not crash mid-warm
            seq = min(seq, max(1, max_seq // 2))
            gen_tokens = min(gen_tokens, max_seq - seq)
            if (seq, gen_tokens) != (args.lm_seq, args.generate_tokens):
                log.info("clamped to seq=%d, generate_tokens=%d (max_seq %d)",
                         seq, gen_tokens, max_seq)
        for b in sorted({int(x) for x in args.batches.split(",") if x.strip()}):
            inputs = {}
            for nm, spec in in_spec.items():
                # the FIRST dynamic dim of each input is the batch axis,
                # later dynamic dims (LM/bert seq, t5 src/tgt) get --lm-seq
                # — unlike the runtime's load-time _concrete_shape (all
                # dims=1), warm must compile the shapes traffic asks for
                shape, dyn = [], 0
                for d in spec.norm_shape():
                    if isinstance(d, str):
                        shape.append(b if dyn == 0 else seq)
                        dyn += 1
                    else:
                        shape.append(d)
                inputs[nm] = np.zeros(tuple(shape), spec.np_dtype())
            rt.predict(mid, inputs)
            compiled.append(f"predict b={b}")
        if family in ("transformer_lm", "moe_lm") and gen_tokens > 0:
            ids = np.zeros((1, seq), np.int32)
            rt.generate(mid, ids, max_new_tokens=gen_tokens)
            compiled.append(f"generate b=1 new={gen_tokens}")
    finally:
        rt.close()
    dt = time.perf_counter() - t0
    print(
        f"warmed {mid} ({family}): {', '.join(compiled)} in {dt:.1f}s -> "
        f"{cache_dir}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

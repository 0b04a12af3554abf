"""Model family registry + the TPUSavedModel artifact format.

The reference serves opaque TF SavedModels through an external
tensorflow_model_server; models here are native JAX modules, stored as a
versioned artifact directory (same ``<base>/<name>/<version>/`` layout the
protocol and providers assume — reference diskmodelprovider.go:20-44):

    <name>/<version>/
      model.json       — {"format": "tpusc.v2", "family": ..., "config": ...,
                          "params": {"file": "params.bin", "manifest": [...]}}
      params.bin       — raw little-endian leaf bytes, grouped by dtype,
                         16-byte-aligned offsets per the manifest

v2 rationale (cold path = the product): one sequential read, zero-copy
views straight into the packed host->HBM transfer
(runtime.packed_device_put) — no msgpack parse, and a multi-GB llama-class
artifact can stream. ``tpusc.v1`` (flax msgpack) artifacts remain readable.

``family`` selects a builder registered here; the builder returns a
``ModelDef`` whose ``apply`` is a pure jittable function — everything the
runtime compiles and pins to TPU HBM.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import numpy as np

ARTIFACT_FORMAT = "tpusc.v2"
ARTIFACT_FORMAT_V1 = "tpusc.v1"
MODEL_JSON = "model.json"
PARAMS_FILE = "params.msgpack"     # v1 (read-compat)
PARAMS_BIN = "params.bin"          # v2


@dataclass(frozen=True)
class TensorSpec:
    """Shape entries are ints (static) or axis-name strings (dynamic): the
    same name must agree across all inputs of one request and buckets
    independently of other names ("batch" + "seq" for LMs, "src"/"tgt" for
    encoder-decoders). -1 is accepted as an alias for "batch"."""

    dtype: str
    shape: tuple[int | str, ...]

    def norm_shape(self) -> tuple[int | str, ...]:
        return tuple("batch" if d == -1 else d for d in self.shape)

    def dynamic_axes(self) -> list[tuple[int, str]]:
        return [(i, d) for i, d in enumerate(self.norm_shape()) if isinstance(d, str)]

    def np_dtype(self) -> np.dtype:
        import ml_dtypes  # registered extended dtypes (bfloat16)

        del ml_dtypes
        return np.dtype(self.dtype)


@dataclass(frozen=True)
class CacheRow:
    """What one token leaves in one layer's cache, the unit the dense cache
    and the paged arena (models/generation.py) are made of: ``sides`` arrays
    of ``(heads, width)`` rows. A decoder with K and V rows has two sides of
    ``(n_kv_heads, head_dim)``; latent attention has ONE side of one shared
    row a token (``[c_kv | rope(k_r)]``, stored ``width`` wide), which every
    query head reads as its key and, in its first ``value_width`` columns, as
    its value.

    ``window`` > 0 makes it the row of a WINDOW layer: a query at position
    ``i`` reads the rows of positions ``(i - window, i]`` and no other, so a
    lane keeps one window of pages in that layer whatever its request's
    length (a ring of ``window / page_tokens + 1`` pages in an arena of its
    own beside the global one, ``generation.init_paged_cache``); 0 = every
    row is kept (a global layer). One declaration a layer in
    ``ModelDef.layer_state``, read through ``generation._layer_slots``."""

    sides: int
    heads: int
    width: int
    value_width: int = 0     # latent rows only: the value is a prefix of the key
    window: int = 0          # > 0: the layer keeps the last ``window`` rows only


@dataclass(frozen=True)
class LaneState:
    """What a request keeps in a layer whose state does NOT grow with its
    length: ``rows`` rows of ``width``, fixed for the life of the request (a
    gated short convolution keeps its last ``kernel - 1`` inputs). It lives
    beside the paged arena, one slice a lane
    (``SlotDecodeState.lane_state``), not in pages.

    ``dtype`` ("" = the model's) is what the part is stored in: a scanned
    state stays float32 whatever the model computes in. ``beside`` are further
    PARTS of the same layer's state, each a ``LaneState`` with an array of its
    own (a Mamba layer keeps its last 3 convolution inputs in the model's
    dtype and a ``(state, channels)`` float32 scan state): the layer's state
    is then the tuple ``(this part, *beside)``, one array a part, and a model's
    lane-state layers all declare the same parts.

    ``operator`` is the layer's operator half, brought by the family:
    ``(layer params, x, state, real_len, cfg) -> (residual delta, the state
    after real_len of the tokens at hand, extras)`` with ``x`` the residual
    stream BEFORE the layer's norm, ``state`` the lane's slice (an array, or
    a tuple a part) and ``extras`` None or a dict the layers after it read
    (``generation._lane_layer`` calls it; a module-level function, so that
    the declaration stays hashable and equal across builds).

    ``step`` (optional) is the operator's ONE-TOKEN form on the state arrays
    where they lie, for a state too large to read and write whole every
    step: ``(layer params, x (S, 1, d), lane, index, took, live, cfg) ->
    (residual delta, lane after, extras)`` with ``lane`` the model's WHOLE
    lane-state arrays ``(lane layers, S, rows, width)`` (a tuple a part),
    ``index`` this layer's, ``took (S,)`` the lanes whose token is real (None
    = all) and ``live`` ``generation._live_lanes`` of them (or None). It
    touches the slices of the lanes that took a token and no other, in place
    on the decode chunk's donated carry. The paged decode step calls it
    where a declaration brings one (``generation._walk_layers``); every other
    forward, and a declaration without it, takes ``operator`` on the layer's
    slice."""

    rows: int
    width: int
    dtype: str = ""
    beside: tuple = ()
    # not part of the declaration's identity: two layers that keep the same
    # parts compare equal (a program's static key also holds the family's name
    # and its whole config, which is what tells two operators apart)
    operator: Callable | None = field(default=None, compare=False)
    step: Callable | None = field(default=None, compare=False)

    def parts(self) -> tuple:
        """The arrays a layer of this kind keeps: ``(rows, width, dtype)``
        a part."""
        return ((self.rows, self.width, self.dtype),
                *(p for b in self.beside for p in b.parts()))


@dataclass(frozen=True)
class SharedRows:
    """A layer that keeps NOTHING of a request and reads the rows another
    layer keeps: model layer ``layer``'s pages, through the same block table
    at the same positions (a cross-attention layer of a decoder whose ONE
    full-attention layer's K/V every later attention layer reads). It has a
    query and an output projection only and writes no row."""

    layer: int


@dataclass(frozen=True)
class NoState:
    """A layer that keeps nothing at all and reads no cache: its operator maps
    the token at hand and what earlier layers of the SAME forward handed on
    (a lane-state operator's ``extras``) to a residual delta,
    ``(layer params, x, handed, cfg) -> delta`` (a gated memory unit)."""

    operator: Callable


def head_width(cfg: Mapping[str, Any]) -> int:
    """A decoder-LM family's head width: its ``head_dim`` where the config
    states one (a model whose heads are not ``d_model / n_heads`` wide), else
    ``d_model / n_heads`` — the one place that derives it."""
    return int(cfg.get("head_dim") or
               int(cfg["d_model"]) // int(cfg["n_heads"]))


def query_heads(cfg: Mapping[str, Any], depth: int | None = None) -> int:
    """How many query heads layer ``depth`` has: ``n_heads_per_layer[depth]``
    where the config states a count a layer (window layers of 72 beside global
    ones of 48 over the same KV heads), else ``n_heads`` in every layer.
    ``depth`` None = the most any layer has. The row a layer keeps does not
    follow it: the KV side is ``kv_cache_row``'s in every layer."""
    per_layer = cfg.get("n_heads_per_layer")
    if not per_layer:
        return int(cfg["n_heads"])
    return int(max(per_layer) if depth is None else per_layer[depth])


def kv_cache_row(cfg: Mapping[str, Any], window: int = 0) -> CacheRow:
    """The decoder-LM families' row: K and V of ``(n_kv_heads, head_width)``;
    ``window`` > 0 for a layer that keeps the last ``window`` rows only."""
    return CacheRow(2, int(cfg["n_kv_heads"]), head_width(cfg),
                    window=int(window))


def latent_cache_row(cfg: Mapping[str, Any]) -> CacheRow:
    """The latent-attention row ``[c_kv | rope(k_r)]``: ``kv_lora_rank +
    qk_rope_head_dim`` columns, stored padded with zeros to a whole number of
    128-lane tiles (Mosaic slices an HBM operand in whole tiles only; the pad
    multiplies zero query columns), the value its first ``kv_lora_rank``."""
    used = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    return CacheRow(1, 1, -(-used // 128) * 128, int(cfg["kv_lora_rank"]))


@dataclass
class ModelDef:
    """A built, servable model family instance.

    ``apply(params, inputs) -> outputs`` is a pure function over a params
    pytree and a dict of arrays — the unit of XLA compilation.
    """

    family: str
    config: dict[str, Any]
    apply: Callable[[Any, Mapping[str, Any]], dict[str, Any]]
    init: Callable[[Any], Any]                      # rng -> params pytree
    input_spec: dict[str, TensorSpec]
    output_spec: dict[str, TensorSpec]
    method_name: str = "tensorflow/serving/predict"
    # canonical (family, config) identity assigned by build(); the runtime
    # keys shared executables by this
    cache_key: str = ""
    # mesh-axis partition rules for multi-chip serving, e.g.
    # {("dense", "kernel"): (None, "model")}; consumed by parallel.sharding
    partition_rules: dict[str, Any] = field(default_factory=dict)
    # hard upper bound per named dynamic axis (e.g. {"seq": max_seq} for
    # absolute-position-table models): the runtime clamps its power-of-two
    # padding bucket to the cap and rejects true sizes beyond it
    axis_caps: dict[str, int] = field(default_factory=dict)
    # loss(params, inputs, targets) for families that support training steps
    loss: Callable[..., Any] | None = None
    # optional derived outputs computed OUTSIDE the jitted apply, on device,
    # from (device_outputs, dyn_sizes): name -> (fn, spec). Lets a client
    # request e.g. "last_token_logits" so predict ships a (B, V) slice
    # instead of the full (B, S, V) logits to host (VERDICT.md weak #4).
    # Only materialized when named in the request's output_filter.
    derived_outputs: dict[str, tuple[Callable[..., Any], TensorSpec]] = field(
        default_factory=dict
    )
    # outputs served when a request names none (output_filter unset). LM
    # families default to ["last_token_logits"]: shipping the full padded
    # (B, S, V) logits tensor per request made warm REST 0.5 qps — clients
    # wanting everything ask for it explicitly (output_filter=["logits"]).
    default_outputs: list[str] | None = None
    # float params are cast to this dtype when the artifact is written (the
    # family's apply casts weights to its compute dtype anyway): a bf16
    # artifact halves both disk reads and the host->device transfer that
    # dominates the cold-miss path.
    store_param_dtype: str | None = None
    # mesh-aware apply factory: families whose computation itself needs the
    # chip-group mesh (ring/context-parallel attention) set this; the runtime
    # jit-compiles bind_mesh(mesh) instead of ``apply`` when serving on a
    # group. Plain TP families leave it None — their sharding is declarative
    # (partition_rules) and XLA inserts the collectives.
    bind_mesh: Callable[[Any], Callable[[Any, Mapping[str, Any]], dict[str, Any]]] | None = None
    # the family declares that the continuous engine may serve it: every
    # layer's per-request state is one of the kinds ``layer_state`` declares
    # (the rows ``cache_row`` describes, one a token, in the paged arena; or a
    # fixed ``LaneState`` a lane beside it), and its step is row-invariant — a
    # row's logits do not depend on the rows beside it, so strangers can
    # share a decode step. The engine and the arena ask this, not the name.
    engine_ready: bool = False
    # what a cache row is (``CacheRow``: sides, heads, width): the dense cache,
    # the paged arena and its byte accounting are built from it. Every
    # ``engine_ready`` family declares one.
    cache_row: CacheRow | None = None
    # what each layer keeps of a request, one entry a layer: the model's
    # ``cache_row`` (the layer has pages in the arena), a ``LaneState`` (a
    # fixed state a lane), ``SharedRows`` (nothing: it reads another layer's
    # pages) or ``NoState`` (nothing at all). A family whose layers are all of
    # one kind declares ``cache_row`` alone and gets ``(cache_row,) * n_layers``.
    layer_state: tuple = ()

    def __post_init__(self) -> None:
        if not self.layer_state and self.cache_row is not None:
            self.layer_state = (self.cache_row,) * int(self.config["n_layers"])


def static_config(model: ModelDef) -> tuple:
    """The hashable form of a family's config that the programs of
    models/generation.py are specialised on (their static ``cfg_key``): the
    config's items, sorted, under ``cache_row`` the row the ModelDef declares
    and, for a model whose layers are not all of that kind, under
    ``layer_state`` what each layer keeps. The one place a row or a layer's
    kind enters shared code: nothing there derives either from a config key
    of some family. (A model of one kind carries no ``layer_state`` item, so
    its programs' key is the one it was.)"""
    items = {k: _hashable(v) for k, v in model.config.items()}
    if model.cache_row is not None:
        items["cache_row"] = model.cache_row
    if any(kind != model.cache_row for kind in model.layer_state):
        items["layer_state"] = tuple(model.layer_state)
    return tuple(sorted(items.items()))


def _hashable(value):
    """A config value as a program's static key can hold it: a list a tuple,
    a mapping the sorted tuple of its items (``dict()`` of it is the mapping
    again)."""
    if isinstance(value, Mapping):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_hashable(v) for v in value)
    return value


def lane_layers(layer_state) -> tuple[int, ...]:
    """The layers (model indices) that keep a fixed ``LaneState``."""
    return tuple(i for i, s in enumerate(layer_state)
                 if isinstance(s, LaneState))


def window_layers(layer_state) -> tuple[int, ...]:
    """The layers (model indices) that keep one window of rows a lane."""
    return tuple(i for i, s in enumerate(layer_state)
                 if isinstance(s, CacheRow) and s.window)


_REGISTRY: dict[str, Callable[[dict[str, Any]], ModelDef]] = {}
_DEFAULT_CONFIGS: dict[str, dict[str, Any]] = {}


def register(name: str, default_config: dict[str, Any] | None = None):
    def deco(builder: Callable[[dict[str, Any]], ModelDef]):
        _REGISTRY[name] = builder
        _DEFAULT_CONFIGS[name] = default_config or {}
        return builder

    return deco


def families() -> list[str]:
    _load_builtin_families()
    return sorted(_REGISTRY)


_BUILD_CACHE: dict[str, ModelDef] = {}  # guarded-by: _BUILD_LOCK
_BUILD_LOCK = threading.Lock()


def build(family: str, config: dict[str, Any] | None = None) -> ModelDef:
    """Build (memoized) a family instance.

    Memoization is load-bearing for multi-tenant serving performance: every
    tenant artifact of the same (family, config) shares ONE ModelDef, hence
    one ``apply`` function identity, hence one jit cache entry and one XLA
    executable — tenant N's cold load skips compilation entirely and costs
    only the params fetch + device_put. The reference cannot do this: TF
    Serving compiles/loads each SavedModel independently.
    """
    _load_builtin_families()
    if family not in _REGISTRY:
        raise KeyError(f"unknown model family {family!r}; known: {families()}")
    merged = dict(_DEFAULT_CONFIGS[family])
    merged.update(config or {})
    key = f"{family}|{json.dumps(merged, sort_keys=True, default=str)}"
    with _BUILD_LOCK:  # one ModelDef identity per key, even under racing loads
        model = _BUILD_CACHE.get(key)
        if model is None:
            model = _REGISTRY[family](merged)
            model.cache_key = key
            _BUILD_CACHE[key] = model
    return model


_BUILTIN_MODULES = (
    "half_plus_two", "mnist_cnn", "bert", "resnet", "transformer_lm", "t5", "moe_lm",
    "mla_moe_lm", "hybrid_lm", "sambay_lm", "olmo_hybrid_lm", "kda_moe_lm",
)


def _load_builtin_families() -> None:
    # import for registration side effects; cheap and idempotent
    import importlib

    for mod in _BUILTIN_MODULES:
        try:
            importlib.import_module(f"tfservingcache_tpu.models.{mod}")
        except ModuleNotFoundError as e:
            if f"models.{mod}" not in str(e):
                raise  # a real dependency error inside the module


# ---------------------------------------------------------------------------
# Artifact IO
# ---------------------------------------------------------------------------

class ArtifactError(Exception):
    pass


def _leaf_path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


_ALIGN = 16  # every leaf offset 16-byte aligned: valid frombuffer views for
             # any dtype, and friendly to vectorized host copies

# int8 transport quantization floor: leaves below this many elements (norms,
# biases, small projections) stay in their float dtype — their bytes are
# noise on the transfer and their dynamic range matters more
_QUANT_MIN_ELEMS = 65536


class QuantLeaf:
    """An int8-transported weight: ``q`` (int8) + per-output-channel
    ``scale`` (f32), dequantized to ``orig_dtype`` ON DEVICE after the
    host->HBM transfer. Registered as a pytree node (lazily, on first
    construction — a module-level registration would force the jax import
    on every light consumer of the registry) so ``packed_device_put`` ships
    q in the int8 group and scale in the f32 group without special-casing."""

    def __init__(self, q, scale, orig_dtype: str) -> None:
        _register_quantleaf()
        self.q = q
        self.scale = scale
        self.orig_dtype = orig_dtype

    def dequant_host(self) -> np.ndarray:
        return (
            np.asarray(self.q).astype(np.float32) * np.asarray(self.scale)
        ).astype(np.dtype(self.orig_dtype))


def _quantleaf_flatten(ql: QuantLeaf):
    return (ql.q, ql.scale), ql.orig_dtype


def _quantleaf_unflatten(aux, children):
    return QuantLeaf(children[0], children[1], aux)


_QUANTLEAF_REGISTERED = False


def _register_quantleaf() -> None:
    global _QUANTLEAF_REGISTERED
    if _QUANTLEAF_REGISTERED:
        return
    import jax

    try:
        jax.tree_util.register_pytree_node(
            QuantLeaf, _quantleaf_flatten, _quantleaf_unflatten
        )
    except ValueError:
        pass  # already registered (re-import)
    _QUANTLEAF_REGISTERED = True


def _quantize_int8(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-output-channel (last axis) symmetric int8: scale = amax/127 over
    the reduced axes. The standard weight-only deployment recipe — relative
    error ~0.4% on smooth weights, invisible next to bf16 compute."""
    af = a.astype(np.float32)
    reduce_axes = tuple(range(a.ndim - 1))
    amax = np.max(np.abs(af), axis=reduce_axes, keepdims=True)
    scale = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
    q = np.clip(np.round(af / scale), -127, 127).astype(np.int8)
    return q, scale


def save_artifact(dest_dir: str, model: ModelDef, params: Any,
                  quantize: str | None = None) -> str:
    """``quantize="int8"`` stores large float weights as int8 + per-channel
    f32 scales: the host->HBM transfer that dominates the cold-miss path
    ships ~half the bytes of a bf16 artifact (~quarter of f32), and the
    runtime dequantizes on device. Opt-in per export — outputs differ from
    the unquantized artifact by the quantization error."""
    import jax

    if quantize not in (None, "int8"):
        raise ArtifactError(f"unsupported quantize scheme {quantize!r}")
    os.makedirs(dest_dir, exist_ok=True)
    if model.store_param_dtype:
        nd = np.dtype(model.store_param_dtype)

        def cast(x):
            if isinstance(x, QuantLeaf):
                return x
            a = np.asarray(x)
            return a.astype(nd) if a.dtype.kind == "f" and a.dtype != nd else a

        params = jax.tree_util.tree_map(
            cast, params, is_leaf=lambda x: isinstance(x, QuantLeaf)
        )

    # QuantLeaf inputs (a raw_quant re-save, e.g. cli repack) are carried
    # through VERBATIM — dequantize-then-requantize would shift scales and
    # compound error on every repack
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantLeaf)
    )

    def _leaf_dtype_name(leaf) -> str:
        if isinstance(leaf, QuantLeaf):
            return "int8"
        return np.asarray(leaf).dtype.name

    # group by dtype so the runtime's per-dtype packed transfer reads
    # contiguous file segments. dtype NAME, not .str: extension dtypes
    # (bfloat16) stringify to the void '|V2' under .str and would not
    # round-trip through np.dtype()
    flat = sorted(
        enumerate(flat), key=lambda e: (_leaf_dtype_name(e[1][1]), e[0])
    )
    manifest = []
    offset = 0
    # leaves stream straight to disk — a llama-class artifact must not hold
    # a second full copy of its params in host memory during export
    with open(os.path.join(dest_dir, PARAMS_BIN), "wb") as f:
        def write_aligned(buf: bytes) -> int:
            nonlocal offset
            pad = (-offset) % _ALIGN
            if pad:
                f.write(b"\0" * pad)
                offset += pad
            start = offset
            f.write(buf)
            offset += len(buf)
            return start

        def write_quant(entry, q, scale, orig_dtype: str):
            entry["dtype"] = "int8"
            entry["offset"] = write_aligned(q.tobytes())
            entry["nbytes"] = q.nbytes
            entry["quant"] = {
                "orig_dtype": orig_dtype,
                "scale_dtype": "float32",
                "scale_shape": list(scale.shape),
                "scale_offset": write_aligned(scale.tobytes()),
                "scale_nbytes": scale.nbytes,
            }

        for _, (path, leaf) in flat:
            if isinstance(leaf, QuantLeaf):
                q = np.ascontiguousarray(np.asarray(leaf.q))
                entry = {"path": _leaf_path_str(path), "shape": list(q.shape)}
                write_quant(entry, q,
                            np.ascontiguousarray(np.asarray(leaf.scale)),
                            leaf.orig_dtype)
                manifest.append(entry)
                continue
            a = np.ascontiguousarray(np.asarray(leaf))
            entry = {
                "path": _leaf_path_str(path),
                "dtype": a.dtype.name,
                "shape": list(a.shape),
            }
            # extension float dtypes (bfloat16) report kind 'V', not 'f' —
            # match by name too or every bf16 artifact would silently skip
            # quantization
            is_float = a.dtype.kind == "f" or a.dtype.name in (
                "bfloat16", "float16"
            )
            if (
                quantize == "int8"
                and is_float
                and a.ndim >= 2
                and a.size >= _QUANT_MIN_ELEMS
            ):
                q, scale = _quantize_int8(a)
                write_quant(entry, q, scale, a.dtype.name)
            else:
                # tobytes, not .data: extension dtypes (bfloat16) have no
                # buffer protocol; copies one leaf at a time, never the tree
                entry["offset"] = write_aligned(a.tobytes())
                entry["nbytes"] = a.nbytes
            manifest.append(entry)
    meta = {
        "format": ARTIFACT_FORMAT,
        "family": model.family,
        "config": model.config,
        "param_dtype": model.store_param_dtype,
        "quantize": quantize,
        "params": {"file": PARAMS_BIN, "manifest": manifest},
        "signature": {
            "inputs": {k: [v.dtype, list(v.shape)] for k, v in model.input_spec.items()},
            "outputs": {k: [v.dtype, list(v.shape)] for k, v in model.output_spec.items()},
            "method_name": model.method_name,
        },
    }
    # model.json LAST: its presence marks the artifact complete (providers
    # stage into unique dirs, but a direct writer gets the same safety)
    with open(os.path.join(dest_dir, MODEL_JSON), "w") as f:
        json.dump(meta, f, indent=1)
    return dest_dir


def load_artifact(path: str, raw_quant: bool = False) -> tuple[ModelDef, Any]:
    """-> (ModelDef, params pytree). Raises ArtifactError on malformed dirs.

    ``raw_quant=True`` returns int8-quantized leaves as ``QuantLeaf`` views
    (q + scale) instead of dequantizing on the host — the runtime's packed
    transfer ships those raw bytes and dequantizes on DEVICE, which is the
    whole point of the int8 artifact. Generic callers keep the default and
    get ordinary float arrays."""
    meta_path = os.path.join(path, MODEL_JSON)
    if not os.path.exists(meta_path):
        raise ArtifactError(f"not a TPUSavedModel artifact (no {MODEL_JSON}): {path}")
    with open(meta_path) as f:
        meta = json.load(f)
    fmt = meta.get("format")
    if fmt == ARTIFACT_FORMAT_V1:
        from flax import serialization

        model = build(meta["family"], meta.get("config"))
        with open(os.path.join(path, PARAMS_FILE), "rb") as f:
            # msgpack_restore avoids needing an init()-built template
            params = serialization.msgpack_restore(f.read())
        return model, _restore_lists(params)
    if fmt != ARTIFACT_FORMAT:
        raise ArtifactError(f"unsupported artifact format {fmt!r} in {path}")
    model = build(meta["family"], meta.get("config"))
    spec = meta.get("params") or {}
    bin_path = os.path.join(path, spec.get("file", PARAMS_BIN))
    manifest = spec.get("manifest")
    if manifest is None or not os.path.exists(bin_path):
        raise ArtifactError(f"artifact missing params manifest or {bin_path}")
    # ONE sequential read; every leaf is a zero-copy aligned view into it
    blob = np.fromfile(bin_path, dtype=np.uint8)
    return model, params_from_manifest(meta, blob, raw_quant=raw_quant,
                                       src=bin_path)


def params_from_manifest(meta: dict[str, Any], blob: np.ndarray,
                         raw_quant: bool = False,
                         src: str = "params blob") -> Any:
    """Rebuild the params pytree from a v2 ``model.json`` dict plus the
    raw ``params.bin`` bytes as a uint8 array — the manifest walk of
    ``load_artifact`` without the filesystem. Peer param distribution
    (protocol/peer_transfer.py) feeds this the byte image it assembled in
    RAM off the wire, so the receiver's packed entry never waits on a
    disk round-trip. Leaves are zero-copy views into ``blob``."""
    manifest = (meta.get("params") or {}).get("manifest")
    if manifest is None:
        raise ArtifactError(f"missing params manifest for {src}")
    import ml_dtypes  # registers bfloat16/float8 names with np.dtype

    del ml_dtypes
    nested: dict[str, Any] = {}
    for ent in manifest:
        dt = np.dtype(ent["dtype"])
        n = int(np.prod(ent["shape"])) if ent["shape"] else 1
        off, nbytes = int(ent["offset"]), int(ent["nbytes"])
        if nbytes != n * dt.itemsize or off + nbytes > blob.nbytes:
            raise ArtifactError(
                f"corrupt manifest entry {ent['path']!r} in {src}"
            )
        arr = np.frombuffer(blob.data, dtype=dt, count=n, offset=off).reshape(
            ent["shape"]
        )
        quant = ent.get("quant")
        if quant is not None:
            sdt = np.dtype(quant.get("scale_dtype", "float32"))
            sn = int(np.prod(quant["scale_shape"])) if quant["scale_shape"] else 1
            soff, snb = int(quant["scale_offset"]), int(quant["scale_nbytes"])
            if snb != sn * sdt.itemsize or soff + snb > blob.nbytes:
                raise ArtifactError(
                    f"corrupt quant scales for {ent['path']!r} in {src}"
                )
            scale = np.frombuffer(
                blob.data, dtype=sdt, count=sn, offset=soff
            ).reshape(quant["scale_shape"])
            ql = QuantLeaf(arr, scale, quant["orig_dtype"])
            arr = ql if raw_quant else ql.dequant_host()
        if ent["path"] == "":
            return arr  # params was a single bare array
        node = nested
        parts = ent["path"].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = arr
    return _restore_lists(nested)


def load_artifact_meta(path: str) -> dict[str, Any]:
    """Parse an artifact's ``model.json`` alone — no params bytes touched.

    ``path`` may be the artifact directory or the model.json file itself
    (the streaming fetch hands over the staged metadata file while
    params.bin is still in flight). Raises ArtifactError on malformed or
    non-v2 metadata; callers that only want the pipeline hint treat any
    raise as "precompile not possible"."""
    meta_path = path
    if os.path.isdir(path):
        meta_path = os.path.join(path, MODEL_JSON)
    if not os.path.exists(meta_path):
        raise ArtifactError(f"no {MODEL_JSON} at {path}")
    with open(meta_path) as f:
        try:
            meta = json.load(f)
        except ValueError as e:
            raise ArtifactError(f"unparseable {meta_path}: {e}") from e
    if not isinstance(meta, dict) or "family" not in meta:
        raise ArtifactError(f"malformed artifact metadata in {meta_path}")
    return meta


def abstract_params_from_meta(meta: Mapping[str, Any]) -> Any:
    """The POST-dequant params pytree as ``jax.ShapeDtypeStruct`` leaves,
    reconstructed from a v2 manifest alone (None when the format carries no
    manifest, i.e. v1 msgpack).

    This is what makes compile-while-transfer possible: the manifest names
    every leaf's path, shape and (for int8 entries) original float dtype, so
    ``jax.jit(apply).lower(...)`` can run before a single parameter byte has
    landed on the host. The tree structure must match ``load_artifact``'s
    exactly (same nesting, same list restoration) or the AOT executable
    would be traced against a different treedef than the real params."""
    import jax

    import ml_dtypes  # registers bfloat16/float8 names with np.dtype

    del ml_dtypes
    if meta.get("format") != ARTIFACT_FORMAT:
        return None
    manifest = (meta.get("params") or {}).get("manifest")
    if manifest is None:
        return None
    nested: dict[str, Any] = {}
    for ent in manifest:
        quant = ent.get("quant")
        dt = np.dtype(quant["orig_dtype"] if quant else ent["dtype"])
        leaf = jax.ShapeDtypeStruct(tuple(ent["shape"]), dt)
        if ent["path"] == "":
            return leaf  # params was a single bare array
        node = nested
        parts = ent["path"].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return _restore_lists(nested)


def resident_bytes_estimate(path: str) -> int | None:
    """Estimated DEVICE bytes of the artifact's params once servable (None
    if unreadable). For plain artifacts this matches the on-disk param bytes;
    for int8-quantized artifacts each quant leaf dequantizes on device to
    ``orig_dtype`` (2-4x its disk size), so capacity planners (the assignment
    warmer's headroom check) must use this, not disk bytes (ADVICE r4)."""
    try:
        import ml_dtypes  # registers bfloat16/float8 names with np.dtype

        del ml_dtypes
        with open(os.path.join(path, MODEL_JSON)) as f:
            meta = json.load(f)
        manifest = (meta.get("params") or {}).get("manifest")
        if manifest is None:
            return None
        total = 0
        for ent in manifest:
            n = int(np.prod(ent["shape"])) if ent["shape"] else 1
            quant = ent.get("quant")
            dt = np.dtype(quant["orig_dtype"] if quant else ent["dtype"])
            total += n * dt.itemsize
        return total
    except Exception:  # noqa: BLE001 - estimate only; callers fall back
        return None


def _restore_lists(tree: Any) -> Any:
    """flax msgpack round-trips Python lists as {"0": ..., "1": ...} dicts;
    convert them back so families can keep natural list-of-layers params."""
    if isinstance(tree, dict):
        restored = {k: _restore_lists(v) for k, v in tree.items()}
        if restored and all(k.isdigit() for k in restored):
            return [restored[k] for k in sorted(restored, key=int)]
        return restored
    return tree


def export_artifact(
    family: str,
    base_dir: str,
    name: str | None = None,
    version: int = 1,
    config: dict[str, Any] | None = None,
    seed: int = 0,
    quantize: str | None = None,
) -> str:
    """Initialize a family with fresh params and write
    ``<base_dir>/<name>/<version>/`` (used by the CLI, tests and bench).

    Init runs on the host CPU backend: an export is offline tooling, and
    running jax.random on an accelerator would round-trip every fresh
    parameter tensor over the host<->device link just to write it to disk."""
    import jax

    model = build(family, config)
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    if cpu is not None:
        with jax.default_device(cpu):
            params = jax.device_get(model.init(jax.random.PRNGKey(seed)))
    else:
        params = model.init(jax.random.PRNGKey(seed))
    dest = os.path.join(base_dir, name or family, str(version))
    return save_artifact(dest, model, params, quantize=quantize)

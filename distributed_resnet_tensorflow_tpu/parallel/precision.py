"""End-to-end mixed-precision policy: bf16 hot paths, f32 masters.

Compute-side MFU has been flat at ~0.35 on imagenet-rn50 since BENCH_r02
because every hot path still ran f32 end to end. This module is the ONE
resolution point for the two low-precision knobs (docs/precision.md):

  * ``train.precision`` — the TRAINING STEP policy. ``bf16`` computes
    activations/matmuls in bfloat16 while the parameters (and the whole
    optimizer state) stay float32 MASTERS: the model is built with a
    bf16 compute dtype (flax casts params leaf-by-leaf at each op — the
    policy cast that wraps model apply), gradients come out f32 (the
    cast's transpose re-accumulates into the f32 param cotangent), and
    the optimizer update runs entirely in f32. BN moments, softmax and
    the loss already accumulate in f32 by construction (ops/batch_norm
    computes moments in f32; train/loop.make_ce_fn casts logits to f32
    before the softmax). ``off`` (the default) leaves the legacy
    ``model.compute_dtype`` contract untouched — BIT-identical to the
    pre-policy step, the exactness oracle every cast path is tested
    against.
  * ``serve.variants`` — reduced-precision SERVING variants
    (serve/compile_cache.py buckets become (batch, variant)): a ``bf16``
    variant serves from a bf16-cast weight copy through a bf16-compute
    predict step; an ``int8`` variant is WEIGHT-ONLY — kernels quantize
    to int8 with per-output-channel f32 scales (¼ the weight HBM) and
    dequantize into an f32 forward at apply time. Resolved by
    :func:`resolve_serve_variants`.

Checkpoints are policy-agnostic by construction: the masters are f32, so
save/restore and the serving hot swap never see a cast leaf —
:func:`check_master_dtypes` is the guard that keeps that true.

Why no fp16 step: an fp16 TRAINING step needs loss scaling to keep small
gradients out of the subnormal range (bf16 shares f32's exponent and
does not); until a scaler exists, ``train.precision=fp16`` is refused
with that reason rather than silently diverging.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

#: dtypes a policy may name
POLICY_DTYPES = {"bf16": jnp.bfloat16, "fp16": jnp.float16}

#: serving-variant names → COMPUTE dtype (``f32`` is the policy-native
#: full-precision variant every server carries implicitly). ``int8`` is
#: WEIGHT-ONLY: kernels live in HBM as int8 with a per-channel f32 scale
#: (make_variant_cast) and dequantize into the f32 forward at apply time
#: — ¼ the weight bytes per replica, full-precision arithmetic.
SERVE_VARIANT_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                        "int8": jnp.float32}

#: variants whose CAST changes the weight REPRESENTATION (not just the
#: dtype): their predict step must dequantize before model apply
#: (train/loop.Trainer.make_variant_predict_step)
WEIGHT_ONLY_VARIANTS = frozenset({"int8"})

#: per-channel symmetric int8 range (the scale denominator); -128 is
#: excluded so the quantizer stays symmetric around zero
INT8_QMAX = 127.0

#: params below this many dims stay f32 under the int8 variant: biases,
#: LayerNorm/BN scales are tiny (no memory win) and precision-critical
INT8_MIN_NDIM = 2


def quantize_leaf_int8(w):
    """One float leaf → ``{"int8_q", "int8_scale"}``: symmetric
    per-OUTPUT-CHANNEL (last dim) scales, values rounded into [-127,127].
    Works on live arrays and under ``jax.eval_shape`` (pure jnp)."""
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=tuple(range(wf.ndim - 1)),
                   keepdims=False)
    scale = jnp.where(amax > 0, amax / INT8_QMAX, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -INT8_QMAX, INT8_QMAX)
    return {"int8_q": q.astype(jnp.int8),
            "int8_scale": scale.astype(jnp.float32)}


def _is_quantized_leaf(x) -> bool:
    return isinstance(x, dict) and set(x) == {"int8_q", "int8_scale"}


def dequantize_params(params):
    """Inverse of the int8 cast: every ``{"int8_q", "int8_scale"}``
    marker dict becomes ``q * scale`` (f32); untouched leaves pass
    through. XLA fuses the dequant into the consuming matmul, so the
    weights stay int8 at rest and widen on the fly."""
    def deq(x):
        if _is_quantized_leaf(x):
            return x["int8_q"].astype(jnp.float32) * x["int8_scale"]
        return x

    return jax.tree_util.tree_map(deq, params,
                                  is_leaf=_is_quantized_leaf)


@dataclass(frozen=True)
class PrecisionPolicy:
    """Resolved ``train.precision`` for one Trainer: compute in
    ``compute_dtype``, keep ``master_dtype`` parameters/optimizer state."""

    name: str                       # "bf16"
    compute_dtype: Any              # jnp.bfloat16
    master_dtype: Any = jnp.float32

    @property
    def compute_dtype_name(self) -> str:
        return jnp.dtype(self.compute_dtype).name

    def cast_compute(self, x: jax.Array) -> jax.Array:
        """The policy input cast (wraps model apply): float arrays enter
        the model in the compute dtype; integer inputs (raw uint8 crops
        headed for the device augment) pass through untouched."""
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return x.astype(self.compute_dtype)
        return x


def precision_unsupported_reason(cfg) -> Optional[str]:
    """None when ``train.precision`` can apply to this config; else a
    one-line reason (``resolve_precision`` raises it — a precision knob
    that silently trains a different program than requested is exactly
    the failure mode the resolver exists to prevent)."""
    mode = cfg.train.precision
    if mode in ("off", "bf16"):
        return None
    if mode == "fp16":
        return ("an fp16 TRAINING step needs loss scaling to keep small "
                "gradients out of the subnormal range (bf16 shares f32's "
                "exponent range and does not) — use train.precision=bf16")
    return f"unknown train.precision setting {mode!r}"


def resolve_precision(cfg) -> Optional[PrecisionPolicy]:
    """``train.precision`` → a :class:`PrecisionPolicy` or None (off =
    the legacy ``model.compute_dtype`` contract, bit-identical)."""
    mode = cfg.train.precision
    if mode == "off":
        return None
    reason = precision_unsupported_reason(cfg)
    if reason is not None:
        raise ValueError(f"train.precision={mode!r} is unsupported: "
                         f"{reason}")
    return PrecisionPolicy(name=mode, compute_dtype=POLICY_DTYPES[mode])


def resolve_serve_variants(cfg) -> Tuple[str, ...]:
    """``serve.variants`` → validated, deduped variant tuple (order
    preserved; the FIRST entry is the default a variant-less request is
    served from). Unknown names raise with the supported set — a
    misspelled variant must never fall back to silently serving f32."""
    raw = cfg.serve.variants or ("f32",)
    if isinstance(raw, str):
        raw = (raw,)
    out = []
    for v in raw:
        if v not in SERVE_VARIANT_DTYPES:
            raise ValueError(
                f"unknown serve variant {v!r}; supported: "
                f"{sorted(SERVE_VARIANT_DTYPES)}")
        if v not in out:
            out.append(v)
    return tuple(out)


def make_variant_cast(variant: str):
    """``cast(state) -> state`` for one serving variant: float leaves of
    params/batch_stats narrowed to the variant dtype (step/int leaves and
    the optimizer state untouched — serving never reads moments). The
    f32 variant is the identity, so the default server pays nothing.
    Works on live device trees (eager per-leaf casts on the caller
    thread — serve/server.py builds variants at startup and at swap
    boundaries, both single-dispatch-thread safe) AND under
    ``jax.eval_shape`` (serve/compile_cache.py derives each variant's
    abstract state the same way, so the two cannot drift).

    ``int8`` (weight-only, docs/precision.md): every float param leaf
    with ≥ ``INT8_MIN_NDIM`` dims becomes a ``{"int8_q", "int8_scale"}``
    pair — symmetric per-output-channel quantization
    (:func:`quantize_leaf_int8`); biases/norm scales and the
    ``batch_stats`` running moments stay f32 (tiny, precision-critical).
    The matching predict step dequantizes at apply time
    (:func:`dequantize_params` via Trainer.make_variant_predict_step)."""
    if variant in WEIGHT_ONLY_VARIANTS:
        def quant_leaf(x):
            arr = jnp.asarray(x)
            if jnp.issubdtype(arr.dtype, jnp.floating) \
                    and arr.ndim >= INT8_MIN_NDIM:
                return quantize_leaf_int8(arr)
            return x

        def quant(state):
            return state.replace(
                params=jax.tree_util.tree_map(quant_leaf, state.params))

        return quant
    dt = SERVE_VARIANT_DTYPES[variant]
    if dt == jnp.float32:
        return lambda state: state

    def cast_leaf(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return jnp.asarray(x).astype(dt)
        return x

    def cast(state):
        return state.replace(
            params=jax.tree_util.tree_map(cast_leaf, state.params),
            batch_stats=jax.tree_util.tree_map(cast_leaf,
                                               state.batch_stats))

    return cast


def check_master_dtypes(params, master_dtype=jnp.float32) -> None:
    """Raise when any floating param leaf is not a ``master_dtype``
    master. The precision policy's whole checkpoint story — save/restore
    and serve hot-swap staying policy-agnostic — rests on the persisted
    tree being f32; a model that initialized a cast leaf (a param_dtype
    override drifting in) would silently bake the policy into every
    checkpoint it writes."""
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        dt = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") \
            else leaf.dtype
        if jnp.issubdtype(dt, jnp.floating) and dt != jnp.dtype(master_dtype):
            bad.append(f"{jax.tree_util.keystr(path)}:{jnp.dtype(dt).name}")
    if bad:
        raise ValueError(
            f"precision policy requires {jnp.dtype(master_dtype).name} "
            f"master params but found {bad[:5]} — a non-master float leaf "
            "would bake the compute policy into every checkpoint")


class PrecisionStats:
    """Process-global record of the resolved precision policy — what
    the ``{"event": "precision"}`` metrics row (train/hooks.PrecisionHook)
    exports. Written at Trainer build / state-init time (a property of
    the run, not of any step)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._snap: Optional[Dict[str, Any]] = None

    def record_policy(self, policy: Optional[PrecisionPolicy]) -> None:
        with self._lock:
            base = self._snap or {}
            self._snap = {**base,
                          "policy": policy.name if policy else "off",
                          "compute_dtype": policy.compute_dtype_name
                          if policy else None,
                          "master_dtype": jnp.dtype(
                              policy.master_dtype).name if policy
                          else None}

    def record_params(self, params) -> None:
        """Master-tree accounting from the LIVE state: leaf count and f32
        master bytes (what checkpoints persist regardless of policy)."""
        leaves = jax.tree_util.tree_leaves(params)
        nbytes = sum(int(l.size) * jnp.dtype(l.dtype).itemsize
                     for l in leaves)
        with self._lock:
            base = self._snap or {}
            self._snap = {**base, "param_leaves": len(leaves),
                          "master_param_bytes": int(nbytes)}

    def reset(self) -> None:
        with self._lock:
            self._snap = None

    def snapshot(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._snap) if self._snap is not None else None


#: process-global precision telemetry (one policy resolution per process)
precision_stats = PrecisionStats()

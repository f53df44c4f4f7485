"""Gradient compression for the cross-zone (cross-pod) aggregation hop.

The paper's ``Broadcast``/``Aggregate`` APIs accept application-specified
compression functions (Table II; refs [37] QSGD, [38] signSGD).  These are
the pure-JAX implementations; ``repro.kernels.quantize`` is the Pallas TPU
version of the QSGD hot loop (bit-identical given the same random bits).

Compressed transport (docs/performance.md "compressed transport"): a
``CompressionPolicy`` rides on ``AppHandle.compression`` (or the async
scheduler's ``app_compression`` knob) and governs the *commit* direction
— workers' delta uploads.  ``quantize_delta`` serializes an update
pytree into a ``QuantizedDelta`` (int8 payload + per-chunk f32 scales,
left on the device), ``CommitDelta`` buffers it as-is, and
``ApplyBuffered`` dequantizes *inside* the buffered aggregation
(``kernels.ops.buffered_aggregate_quantized``: per-row scales compose with the
staleness weights in one kernel call, between two compiled programs,
and the aggregate stays on the device).  The scheduler prices commit
flows at ``CompressionPolicy.wire_bytes(model_bytes)``, so the
compressed byte count is what enters ``EventCore.open_flow`` — fair
shares, caps, relay admission and sampled cold loads all see the
smaller flows.  ``kind="none"`` is proven byte-identical to the
uncompressed path (tests/test_compression.py).

Rounding bits: every commit draws its own PRNG key via ``commit_key``
(policy seed -> app -> commit sequence number), so repeated commits do
not share rounding bias — the old deterministic default (``rand=0.5``
everywhere) rounded every commit half-down identically.  A fixed
(policy, app, seq) triple reproduces the wire bytes exactly.

One quantize is two device programs: the grid program (``_grid``: the
rounding key folded from the raw counters, the leaves raveled to f32,
concatenated and zero-padded onto the (rows, chunk) grid, the uniforms
drawn) and the kernel (``kernels.ops.qsgd_quantize``).  The counters
enter as traced values, so one program per model shape serves every
commit and every broadcast.

Compressed downlink (docs/performance.md "compressed downlink"): the
``downlink`` axis governs the *broadcast* direction — the master's
model downloads.  ``"qsgd-int8"`` quantizes each new version before it
ships; ``"delta-qsgd"`` broadcasts ``quantize(params_v+1 - ref_v)``
against a bounded per-app version-delta cache, where ``ref_v`` is the
reference reconstruction every delta-following worker holds (error
feedback on the downlink: the reference absorbs each step's quantizer
error, so drift from the true params stays one quantization bound, it
never compounds).  A worker K versions behind downloads the chained
deltas for its gap; past ``chain_cap`` (or with no cached base at all —
first download, churn rejoin) it falls back to the full f32 state.
Delta payloads pack the small ``downlink_levels`` lattice at
``downlink_bits`` bits per element (``delta_wire_bytes``); the
scheduler prices every broadcast leg at ``downlink_wire_bytes`` and the
fused ``kernels.ops.apply_quantized_broadcast`` kernel folds a whole
chain into the held params in one pass (``apply_delta_chain``).
``AsyncTrainer`` keeps its reference on the device as the (rows, 256)
grid (``reference_grid``): ``quantize_broadcast_delta(...,
reference=)`` subtracts it inside the grid program, the q and scale it
caches stay on the device, and ``advance_reference`` folds each delta
in and pulls the held state once.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro import tracing


def qsgd_quantize(x: jax.Array, *, levels: int = 127, key=None, rand=None):
    """Stochastic int8 quantization with per-row scale.

    x: (..., d).  Returns (q int8, scale f32 (..., 1)).
    ``rand``: optional precomputed uniforms in [0,1) (for bit-exact refs).
    With neither ``key`` nor ``rand``, rounding is deterministic
    round-half-down (``rand=0.5``) — fine for one-shot use, but commits
    must thread a per-commit key (``commit_key``) or they all share the
    same rounding bias.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / levels
    scale = jnp.maximum(scale, 1e-12)
    y = xf / scale
    if rand is None:
        rand = (
            jax.random.uniform(key, x.shape) if key is not None else jnp.full(x.shape, 0.5)
        )
    q = jnp.floor(y + rand).astype(jnp.int8)
    return q, scale


def qsgd_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def signsgd_compress(x: jax.Array):
    """1-bit sign compression with mean-|x| scale."""
    xf = x.astype(jnp.float32)
    scale = jnp.mean(jnp.abs(xf), axis=-1, keepdims=True)
    return jnp.sign(xf).astype(jnp.int8), scale


def signsgd_decompress(s: jax.Array, scale: jax.Array) -> jax.Array:
    return s.astype(jnp.float32) * scale


def topk_sparsify(x: jax.Array, frac: float):
    """Keep the top-``frac`` fraction by |value| (per leading row)."""
    xf = x.astype(jnp.float32)
    flat = xf.reshape(xf.shape[0], -1) if xf.ndim > 1 else xf[None]
    k = max(1, int(flat.shape[-1] * frac))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    out = jnp.zeros_like(flat)
    out = jax.vmap(lambda o, i, f: o.at[i].set(f[i]))(out, idx, flat)
    return out.reshape(xf.shape)


def error_feedback_update(x: jax.Array, err: jax.Array, compress_fn):
    """EF-SGD: compress (x + err), carry the residual forward."""
    target = x.astype(jnp.float32) + err
    c, scale = compress_fn(target)
    approx = c.astype(jnp.float32) * scale
    return (c, scale), target - approx


# -- per-app commit compression policy (bytes on the wire) ---------------------

_KINDS = ("none", "qsgd-int8", "signsgd", "topk")
_DOWNLINK_KINDS = ("none", "qsgd-int8", "delta-qsgd")


@dataclass(frozen=True)
class CompressionPolicy:
    """Per-app compression for both wire directions (paper Table II's
    per-app compression hooks, made first-class for the transport model).

    Commit (uplink) axis — ``kind``: ``"none"`` (full f32 payloads, the
    byte-identical default), ``"qsgd-int8"`` (QSGD stochastic int8, one
    f32 max-abs scale per ``chunk`` elements), ``"signsgd"`` (1-bit sign
    + per-chunk mean-|x| scale, ref [38]), or ``"topk"`` (keep the
    ``topk_frac`` fraction by |value|, QSGD-quantized; wire ships int8
    value + i32 index per survivor).  ``levels`` is the quantization
    grid per sign (<= 127 so the lattice fits int8).  ``seed`` roots the
    per-commit rounding-key chain (``commit_key``).  ``error_feedback``
    turns on EF-SGD: the trainer carries each worker's residual
    ``x - deq(q(x))`` into its next commit, so aggressive ``levels``
    settings stay unbiased over rounds.

    Broadcast (downlink) axis — ``downlink``: ``"none"`` (full f32
    broadcasts, byte-identical to the uncompressed path),
    ``"qsgd-int8"`` (each new version ships quantized at ``levels``), or
    ``"delta-qsgd"`` (version deltas quantized at ``downlink_levels``
    and packed at ``downlink_bits`` bits/element; workers <= ``chain_cap``
    versions behind download the chained deltas, everyone else the full
    f32 state — see the module docstring for the reference-
    reconstruction scheme)."""

    kind: str = "none"
    levels: int = 127
    chunk: int = 256
    seed: int = 0
    topk_frac: float = 0.01
    error_feedback: bool = False
    downlink: str = "none"
    downlink_levels: int = 7
    chain_cap: int = 3

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"compression kind must be one of {_KINDS}, got {self.kind!r}")
        if not 1 <= int(self.levels) <= 127:
            raise ValueError(f"levels must be in [1, 127] (int8 lattice), got {self.levels!r}")
        if int(self.chunk) < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk!r}")
        if not 0.0 < float(self.topk_frac) <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], got {self.topk_frac!r}")
        if self.downlink not in _DOWNLINK_KINDS:
            raise ValueError(
                f"downlink kind must be one of {_DOWNLINK_KINDS}, got {self.downlink!r}"
            )
        if not 1 <= int(self.downlink_levels) <= 127:
            raise ValueError(
                f"downlink_levels must be in [1, 127] (int8 lattice), "
                f"got {self.downlink_levels!r}"
            )
        if int(self.chain_cap) < 1:
            raise ValueError(f"chain_cap must be >= 1, got {self.chain_cap!r}")

    @property
    def enabled(self) -> bool:
        return self.kind != "none"

    @property
    def downlink_enabled(self) -> bool:
        return self.downlink != "none"

    def _rows(self, payload_bytes: float) -> int:
        return max(1, math.ceil(float(payload_bytes) / 4.0 / self.chunk))

    def wire_bytes(self, payload_bytes: float) -> float:
        """Modeled commit bytes on the wire for a ``payload_bytes`` f32
        payload.

        qsgd-int8 serializes n = payload_bytes/4 elements as one int8
        each, padded to whole chunks, plus one f32 scale per chunk —
        exactly ``QuantizedDelta.nbytes`` for a real n-element delta
        (tested).  signsgd bit-packs one sign per element (chunk/8 bytes
        per row) plus the per-chunk f32 scale.  topk ships k = ceil(n *
        topk_frac) survivors as int8 value + i32 index pairs plus the
        per-chunk scales.  ``kind="none"`` returns the input unchanged
        (same float object arithmetic as the uncompressed path, so
        pricing is bit-identical)."""
        if not self.enabled:
            return float(payload_bytes)
        n = float(payload_bytes) / 4.0
        rows = self._rows(payload_bytes)
        if self.kind == "signsgd":
            return float(rows * math.ceil(self.chunk / 8) + rows * 4)
        if self.kind == "topk":
            k = max(1, math.ceil(n * float(self.topk_frac)))
            return float(5 * k + rows * 4)
        return float(rows * self.chunk + rows * 4)

    @property
    def downlink_bits(self) -> int:
        """Bits per element of a packed broadcast delta: the minimal
        fixed width for the 2*downlink_levels+1 lattice points."""
        return max(1, math.ceil(math.log2(2 * int(self.downlink_levels) + 1)))

    def delta_wire_bytes(self, payload_bytes: float) -> float:
        """Modeled bytes of ONE quantized version delta: elements packed
        at ``downlink_bits`` bits plus one f32 scale per chunk.  (The
        in-memory ``QuantizedDelta`` keeps int8 — the packed size is the
        wire model, mirrored in ``QuantizedDelta.wire_nbytes``.)"""
        rows = self._rows(payload_bytes)
        return float(rows * math.ceil(self.chunk * self.downlink_bits / 8) + rows * 4)

    def downlink_wire_bytes(self, payload_bytes: float, chain: int | None = None) -> float:
        """Modeled bytes of one broadcast (download) to one worker.

        ``chain`` is the worker's version gap when it qualifies for the
        delta path (``downlink="delta-qsgd"``, base cached, gap <=
        ``chain_cap``) — ``chain=0`` is a version check with no payload,
        ``chain=k`` ships k cached deltas.  ``chain=None`` means the
        full path: the f32 state for ``delta-qsgd`` fallback (and for
        ``downlink="none"``), the quantized full model for
        ``downlink="qsgd-int8"`` (which never chains)."""
        if self.downlink == "delta-qsgd" and chain is not None:
            if int(chain) < 0:
                raise ValueError(f"delta chain must be >= 0, got {chain!r}")
            return float(chain) * self.delta_wire_bytes(payload_bytes)
        if self.downlink == "qsgd-int8":
            rows = self._rows(payload_bytes)
            return float(rows * self.chunk + rows * 4)
        return float(payload_bytes)


def as_policy(value) -> CompressionPolicy | None:
    """Normalize a policy knob: None, a ``CompressionPolicy``, or a kind
    string (``"qsgd-int8"``)."""
    if value is None or isinstance(value, CompressionPolicy):
        return value
    if isinstance(value, str):
        return CompressionPolicy(kind=value)
    raise TypeError(f"expected CompressionPolicy, kind string or None, got {value!r}")


_BROADCAST_LANE = 0x0D0C  # folded into the seed to root the downlink's keys


@functools.lru_cache(maxsize=64)  # one root per policy seed and lane
def _key_root(seed: int, lane: int | None):
    """Root of a rounding-key chain: ``PRNGKey(seed)``, with ``lane``
    folded in for the downlink."""
    root = jax.random.PRNGKey(seed)
    return root if lane is None else jax.random.fold_in(root, lane)


def _fold(root, app, count):
    return jax.random.fold_in(jax.random.fold_in(root, app), count)


def commit_key(policy: CompressionPolicy, app_idx: int, commit_seq: int):
    """The per-commit rounding key: policy seed -> app -> commit number.

    The sequence number is assigned when the scheduler delivers the
    commit (``AsyncTrainer.commit``), so the chain is deterministic for
    a given event trace: a fixed (seed, app, seq) reproduces the wire
    bytes exactly, while consecutive commits draw decorrelated uniforms
    (tests/test_compression.py).  ``quantize_delta(..., app=, seq=)``
    folds the same key inside its grid program."""
    return _fold(_key_root(int(policy.seed), None), int(app_idx), int(commit_seq))


def broadcast_key(policy: CompressionPolicy, app_idx: int, version: int):
    """The per-broadcast rounding key: seed -> downlink lane -> app ->
    model version.  Folding a fixed lane constant first decorrelates the
    broadcast stream from the commit stream even when (app, version)
    collides with some (app, seq).  ``quantize_broadcast_delta(...,
    app=, version=)`` folds the same key inside its grid program."""
    return _fold(_key_root(int(policy.seed), _BROADCAST_LANE), int(app_idx), int(version))


@dataclass(frozen=True)
class QuantizedDelta:
    """One worker delta serialized for the wire: int8 lattice points +
    per-chunk f32 scales + the pytree structure needed to rebuild it.

    ``q`` is (R, chunk) int8 (the flattened, zero-padded delta), ``scale``
    (R, 1) f32: the device arrays the quantize produced, which a commit
    keeps until ``ApplyBuffered`` aggregates them and ``AsyncTrainer``'s
    delta-qsgd broadcast cache until ``apply_delta_chain`` folds them in
    (a qsgd-int8 broadcast, or a delta built by hand, may hold host
    arrays).  Dequantization is ``q * scale`` row-wise; padding elements
    quantize to exactly 0 (|0/scale + u| < 1 for u in [0, 1)) and are
    dropped by ``unflatten``.

    ``wire_nbytes`` overrides the modeled wire size when the serialized
    format is narrower than the in-memory int8 grid (bit-packed signsgd,
    sparse topk, packed downlink deltas); ``None`` means the arrays ARE
    the wire format (dense qsgd-int8)."""

    q: Any                      # np.ndarray, or a jax.Array on the device
    scale: Any
    length: int                 # unpadded element count
    shapes: tuple               # leaf shapes, flatten order
    treedef: Any
    levels: int
    chunk: int
    wire_nbytes: float | None = None

    @property
    def nbytes(self) -> float:
        """Serialized wire size (what ``CommitDelta`` accounts)."""
        if self.wire_nbytes is not None:
            return float(self.wire_nbytes)
        return float(self.q.nbytes + self.scale.nbytes)

    def unflatten(self, flat) -> Any:
        """Rebuild the delta pytree from a flat (>= length,) f32 vector."""
        vec = tracing.pull(flat)[: self.length]
        leaves, off = [], 0
        for s in self.shapes:
            size = int(np.prod(s)) if s else 1
            leaves.append(vec[off : off + size].reshape(s))
            off += size
        return jax.tree.unflatten(self.treedef, leaves)

    def dequantize(self) -> Any:
        """Unfused reference: dequantize this delta alone (the fused
        apply-side path composes scales with staleness weights instead —
        ``kernels.ops.buffered_aggregate_quantized``).  Device arrays
        multiply on the device, and ``unflatten`` pulls the product once."""
        flat = self.q.astype(np.float32) * self.scale.astype(np.float32)
        return self.unflatten(flat.reshape(-1))


def _on_grid(leaves, chunk: int):
    """The leaves raveled to f32 in flatten order and zero-padded onto
    the (rows, chunk) grid (traced)."""
    flat = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
    n = sum(f.size for f in flat)
    rows = max(1, math.ceil(n / chunk))
    x2d = jnp.concatenate(flat + [jnp.zeros((rows * chunk - n,), jnp.float32)])
    return x2d.reshape(rows, chunk)


@functools.partial(jax.jit, static_argnames=("chunk",))
def reference_grid(leaves, *, chunk: int):
    """A state's leaves on the (rows, chunk) grid, on the device: the
    form ``AsyncTrainer`` keeps the delta-qsgd reference in."""
    return _on_grid(leaves, chunk)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _grid(leaves, key, app, count, ref=None, *, chunk: int):
    """The one program in front of the kernel: the leaves raveled to f32
    in flatten order, zero-padded onto the (rows, chunk) grid, less
    ``ref`` (a grid of the same shape) where given, and the uniforms that
    round it.  ``key`` is a chain root with the traced counters ``app``
    and ``count`` folded in, or with both ``None`` the rounding key
    itself; ``key=None`` rounds half-down (``rand=0.5``)."""
    x2d = _on_grid(leaves, chunk)
    if ref is not None:
        x2d = x2d - ref
    rows = x2d.shape[0]
    if key is None:
        return x2d, jnp.full((rows, chunk), 0.5, jnp.float32)
    if app is not None:
        key = _fold(key, app, count)
    return x2d, jax.random.uniform(key, (rows, chunk), jnp.float32)


def _rounding(key, root, app, count):
    """``_grid``'s rounding arguments: ``key`` as given, or with the raw
    counters (``app``, ``count``) the chain ``root`` to fold them into."""
    if (app is None) != (count is None):
        raise ValueError("pass both counters of the rounding key, or neither")
    if app is None:
        return key, None, None
    if key is not None:
        raise ValueError("pass a rounding key or its counters, not both")
    return root, np.uint32(app), np.uint32(count)


def _to_grid(delta, chunk: int, key, app, count, ref=None):
    """``delta`` (less the grid ``ref``, where given) on the quantization
    grid with its uniforms, from one ``_grid`` dispatch, and what
    rebuilds the pytree."""
    leaves, treedef = jax.tree.flatten(delta)
    shapes = tuple(np.shape(l) for l in leaves)
    x2d, rand = _grid(
        [tracing.implicit_push(l) for l in leaves], key, app, count, ref, chunk=chunk
    )
    return x2d, rand, sum(math.prod(s) for s in shapes), shapes, treedef


def _qsgd_grid(x2d, rand, levels: int):
    """QSGD-quantize one (rows, chunk) grid: one kernel dispatch when the
    chunking matches the Pallas 256-lane row, else the pure-JAX path."""
    if x2d.shape[1] == 256:
        from repro.kernels import ops as kops

        return kops.qsgd_quantize(x2d, rand, levels=levels)
    return qsgd_quantize(x2d, levels=levels, rand=rand)


def quantize_delta(
    delta, policy: CompressionPolicy, key=None, *, app=None, seq=None
) -> QuantizedDelta:
    """Serialize an update pytree under ``policy`` (must be enabled).

    Rounding: ``app`` and ``seq`` (the commit path) fold
    ``commit_key(policy, app, seq)`` inside the grid program; else
    ``key`` is the rounding key, and ``key=None`` rounds half-down
    (tests only).  qsgd-int8 is then one kernel dispatch (``kernels.ops.
    qsgd_quantize``: Pallas on TPU, compiled ref off-TPU) when the
    chunking matches the kernel's 256-lane row; any other ``chunk``
    takes the pure-JAX path — both are bit-identical given the same
    uniforms.  signsgd stores signs on the same int8 grid with a masked
    per-chunk mean-|x| scale (padding rows never dilute the mean); topk
    zeroes everything below the global top-``topk_frac`` cut, then
    QSGD-quantizes the survivors.  All three ride ``QuantizedDelta`` —
    the same buffer, the same fused dequantize-in-aggregate apply path —
    with ``wire_nbytes`` carrying the packed/sparse wire model where the
    int8 grid overstates it.  The returned q and scale are the device
    arrays the kernel (or the pure-JAX path) produced: nothing is pulled,
    and ``ApplyBuffered`` aggregates them where they are.  Counts
    ``quantize_fused`` for a call that was the grid program and one
    kernel dispatch, else ``quantize_eager``."""
    if not policy.enabled:
        raise ValueError("quantize_delta requires an enabled policy (kind != 'none')")
    with tracing.span("quantize"):
        chunk = int(policy.chunk)
        rounding = _rounding(key, _key_root(int(policy.seed), None), app, seq)
        if policy.kind == "signsgd":  # signs draw no uniforms
            rounding = (None, None, None)
        x2d, rand, n, shapes, treedef = _to_grid(delta, chunk, *rounding)
        wire = None
        if policy.kind == "signsgd":
            rows = x2d.shape[0]
            counts = np.clip(n - chunk * np.arange(rows), 1, chunk).astype(np.float32)
            s = jnp.sum(jnp.abs(x2d), axis=-1, keepdims=True) / tracing.implicit_push(counts[:, None])
            q = jnp.sign(x2d).astype(jnp.int8)
            wire = policy.wire_bytes(4.0 * n)
        elif policy.kind == "topk":
            k = max(1, math.ceil(n * float(policy.topk_frac)))
            if n > k:
                flat = x2d.reshape(-1)[:n]
                _, idx = jax.lax.top_k(jnp.abs(flat), k)
                sparse = jnp.zeros_like(flat).at[idx].set(flat[idx])
                rows = x2d.shape[0]
                x2d = jnp.zeros((rows * chunk,), jnp.float32).at[:n].set(sparse)
                x2d = x2d.reshape(rows, chunk)
            q, s = _qsgd_grid(x2d, rand, int(policy.levels))
            wire = policy.wire_bytes(4.0 * n)
        else:
            q, s = _qsgd_grid(x2d, rand, int(policy.levels))
        fused = policy.kind == "qsgd-int8" and chunk == 256
        tracing.count("quantize_fused" if fused else "quantize_eager")
        return QuantizedDelta(
            q=q, scale=s, length=n, shapes=shapes,
            treedef=treedef, levels=int(policy.levels), chunk=chunk,
            wire_nbytes=wire,
        )


def dequantize_delta(qd: QuantizedDelta) -> Any:
    return qd.dequantize()


# -- downlink: version deltas + fused chain application ------------------------


def quantize_broadcast_delta(
    delta, policy: CompressionPolicy, key=None, *, app=None, version=None, reference=None
) -> QuantizedDelta:
    """Serialize one version delta for the broadcast direction: QSGD on
    the coarse ``downlink_levels`` lattice, ``wire_nbytes`` set to the
    bit-packed size (``delta_wire_bytes``) the scheduler prices chained
    downloads at.  Rounding as in ``quantize_delta``: ``app`` and
    ``version`` fold ``broadcast_key(policy, app, version)`` inside the
    grid program, else ``key`` as given.

    With ``reference`` (the workers' held state on the grid, a device
    array: ``reference_grid``) ``delta`` is the new params: the grid
    program subtracts the reference on the grid, the same f32
    subtraction as ``params - reference`` leaf by leaf, and the
    returned q and scale stay on the device."""
    if not policy.downlink_enabled:
        raise ValueError(
            "quantize_broadcast_delta requires an enabled downlink (downlink != 'none')"
        )
    with tracing.span("quantize"):
        chunk = int(policy.chunk)
        root = _key_root(int(policy.seed), _BROADCAST_LANE)
        x2d, rand, n, shapes, treedef = _to_grid(
            delta, chunk, *_rounding(key, root, app, version), ref=reference
        )
        levels = int(policy.downlink_levels) if policy.downlink == "delta-qsgd" else int(policy.levels)
        q, s = _qsgd_grid(x2d, rand, levels)
        tracing.count("quantize_fused" if chunk == 256 else "quantize_eager")
        wire = policy.downlink_wire_bytes(4.0 * n, chain=1)
        if reference is None:
            q, s = tracing.pull(q), tracing.pull(s)
        return QuantizedDelta(
            q=q, scale=s, length=n, shapes=shapes,
            treedef=treedef, levels=levels, chunk=chunk, wire_nbytes=wire,
        )


def apply_delta_chain(params, deltas: list) -> Any:
    """Fold a chain of quantized version deltas into ``params`` in ONE
    fused dequantize-and-apply pass (``kernels.ops.
    apply_quantized_broadcast``; pure-JAX for non-kernel chunkings).

    The deltas are accumulated strictly in chain order, element-wise —
    the same additions, in the same order, as applying them one version
    at a time — so a stale worker folding its whole gap in one call
    lands on the same reconstruction the master maintained
    incrementally.  All deltas must share one (rows, chunk) grid (same
    model, same policy)."""
    if not deltas:
        return params
    with tracing.span("chain"):
        qd0 = deltas[0]
        rows, chunk = qd0.q.shape
        leaves = jax.tree.leaves(params)
        flat = np.concatenate(
            [np.ravel(tracing.pull(l)).astype(np.float32) for l in leaves]
        ) if leaves else np.zeros((0,), np.float32)
        if flat.size != qd0.length:
            raise ValueError(
                f"params have {flat.size} elements but the chain was built for {qd0.length}"
            )
        w2d = np.zeros((rows * chunk,), np.float32)
        w2d[: flat.size] = flat
        w2d = w2d.reshape(rows, chunk)
        q = np.stack([tracing.pull(d.q) for d in deltas])      # (D, rows, chunk) int8
        s = np.stack([tracing.pull(d.scale) for d in deltas])  # (D, rows, 1) f32
        if chunk == 256:
            from repro.kernels import ops as kops

            out = tracing.pull(kops.apply_quantized_broadcast(
                tracing.implicit_push(w2d), tracing.implicit_push(q), tracing.implicit_push(s)))
        else:
            out = w2d
            for d in range(q.shape[0]):
                out = out + q[d].astype(np.float32) * s[d]
        rebuilt = qd0.unflatten(out.reshape(-1))
        return jax.tree.map(
            lambda p, v: np.asarray(v, dtype=tracing.pull(p).dtype), params, rebuilt
        )


def advance_reference(grid, qd: QuantizedDelta, like) -> tuple:
    """Fold one quantized version delta into the reference
    reconstruction kept on the device as its (rows, chunk) ``grid``: one
    ``kernels.ops.apply_quantized_broadcast`` dispatch at the kernel's
    256-wide row, the same additions as ``apply_delta_chain``'s.
    Returns the new grid and the state the workers hold as host leaves
    of ``like``'s structure and dtypes, from one pull.  Where a leaf is
    not float32 the grid is rebuilt from the held leaves, so that it
    holds their rounding."""
    from repro.kernels import ops as kops

    with tracing.span("chain"):
        if qd.chunk == 256:
            grid = kops.apply_quantized_broadcast(grid, qd.q[None], qd.scale[None])
        else:
            grid = grid + qd.q.astype(jnp.float32) * qd.scale
        rebuilt = qd.unflatten(tracing.pull(grid).reshape(-1))
        held = jax.tree.map(lambda p, v: np.asarray(v, dtype=p.dtype), like, rebuilt)
        leaves = jax.tree.leaves(held)
        if any(l.dtype != np.float32 for l in leaves):
            grid = reference_grid([tracing.implicit_push(l) for l in leaves], chunk=qd.chunk)
        return grid, held

"""ONE quantization layer for every byte-bound seam.

Network bytes cap training (PS push/pull, dp gradient aggregation) and
HBM bytes cap serving (KV capacity bounds concurrent slots); EQuARX
(PAPERS.md) shows int8 collectives inside XLA lose negligible quality.
This module is the jax_graft version of that idea, shared verbatim by
three consumers so their error characteristics are identical:

- **PS transport** (``ps/client.py`` / ``ps/server.py``): gradients are
  quantized host-side into a :class:`QuantArray` before ``wire.dumps``
  and dequantized server-side before the optimizer step (pull responses
  symmetrically) — ``HETU_PS_QUANT=int8``.
- **Collectives** (``graph/ops_comm.py``): a quantize→all_gather→
  dequantize comm-op pair over a mesh axis, statically verified by
  ``analysis/shard_check.py`` — ``HETU_COMM_QUANT=int8``.
- **Serving KV** (``serving/kv_manager.py`` + the decode kernels): an
  int8 KV pool with per-(position, head) scales, dequantized inside the
  online-softmax loop — ``HETU_KV_QUANT=int8``.

Scheme: SYMMETRIC per-chunk int8.  A chunk of values shares one f32
scale ``amax / 127``; encode is ``round(x / scale)`` clipped to
[-127, 127], decode ``q * scale``.  Per-element error is bounded by
``scale / 2 = amax / 254`` — ~0.4% of the chunk's largest magnitude —
which is the tolerance every parity gate in ``tests/test_quant.py``
tests against.  All-zero chunks encode with scale 1.0 so decode is
exactly zero.  The jax half is pure ``jnp`` (traces, shards, vmaps);
the numpy half never touches a device (PS servers must not grab one).

Everything here is OFF by default: with the three knobs unset, no call
site changes a single byte of behavior.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from . import envvars

# elements per scale on the PS wire (flat chunking of arbitrary shapes);
# small enough that one outlier only poisons 256 neighbors, big enough
# that scale overhead is ~1.5% of the int8 payload
DEFAULT_CHUNK = 256

_Q_MODES = ("int8",)


def resolve_quant(mode, env_name):
    """Shared knob grammar: an explicit ``mode`` wins ("int8" enables,
    None/""/"0"/"off" disables); else the registered env var decides.
    Returns "int8" or None."""
    if mode is None:
        mode = envvars.get_str(env_name)
    if mode is None:
        return None
    s = str(mode).strip().lower()
    if s in ("", "0", "off", "none", "false"):
        return None
    if s in _Q_MODES:
        return s
    raise ValueError(
        f"unknown quantization mode {mode!r} (via {env_name}); "
        f"supported: {_Q_MODES}")


def ps_quant():
    return resolve_quant(None, "HETU_PS_QUANT")


def comm_quant():
    return resolve_quant(None, "HETU_COMM_QUANT")


# --------------------------------------------------------------------- #
# numpy half: the PS wire codec (host-side, device-free)
# --------------------------------------------------------------------- #

def quantize_np(x, chunk=DEFAULT_CHUNK):
    """Flat per-chunk symmetric int8 encode of a float array: returns
    (q int8 [x.size], scales f32 [ceil(size/chunk)]).  The trailing
    partial chunk is padded with zeros for the scale reduction only —
    ``q`` keeps exactly ``x.size`` elements."""
    flat = np.ascontiguousarray(x, np.float32).reshape(-1)
    n = flat.size
    chunk = int(chunk)
    n_chunks = max(-(-n // chunk), 1)
    padded = np.zeros(n_chunks * chunk, np.float32)
    padded[:n] = flat
    amax = np.abs(padded.reshape(n_chunks, chunk)).max(axis=1)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.rint(padded.reshape(n_chunks, chunk) / scales[:, None])
    q = np.clip(q, -127, 127).astype(np.int8).reshape(-1)[:n]
    return q, scales


def dequantize_np(q, scales, chunk=DEFAULT_CHUNK):
    """Inverse of :func:`quantize_np` (flat f32 [q.size])."""
    q = np.asarray(q, np.int8).reshape(-1)
    chunk = int(chunk)
    n_chunks = len(scales)
    padded = np.zeros(n_chunks * chunk, np.float32)
    padded[:q.size] = q.astype(np.float32)
    out = padded.reshape(n_chunks, chunk) * \
        np.asarray(scales, np.float32)[:, None]
    return out.reshape(-1)[:q.size]


class QuantArray:
    """A quantized ndarray in flight on the PS wire: the int8 payload,
    its per-chunk f32 scales, and the original shape/dtype.  The wire
    codec (``ps/wire.py`` tag ``Q``) carries this pair natively; the
    receiving side calls :meth:`decode` (servers before the optimizer
    step, clients after a quantized pull)."""

    __slots__ = ("q", "scales", "shape", "dtype", "chunk")

    def __init__(self, q, scales, shape, dtype="<f4", chunk=DEFAULT_CHUNK):
        self.q = q
        self.scales = scales
        self.shape = tuple(int(d) for d in shape)
        self.dtype = str(dtype)
        self.chunk = int(chunk)

    @classmethod
    def encode(cls, x, chunk=DEFAULT_CHUNK):
        x = np.asarray(x)
        q, scales = quantize_np(x, chunk)
        return cls(q, scales, x.shape, np.dtype(np.float32).str, chunk)

    def decode(self):
        out = dequantize_np(self.q, self.scales, self.chunk)
        return out.reshape(self.shape).astype(np.dtype(self.dtype))

    @property
    def nbytes(self):
        return self.q.nbytes + self.scales.nbytes

    def __repr__(self):
        return (f"QuantArray(shape={self.shape}, chunk={self.chunk}, "
                f"{self.nbytes}B)")


def maybe_decode(x):
    """``x.decode()`` when ``x`` is a :class:`QuantArray`, else ``x``
    unchanged — the one-line guard every PS server verb uses."""
    return x.decode() if isinstance(x, QuantArray) else x


# float payloads smaller than this many elements stay f32 on the wire:
# below it the scale/metadata overhead eats the win, and exactness of
# tiny control-plane arrays (row-shard metadata, 0-d scalars) is worth
# more than a handful of bytes
WIRE_MIN_SIZE = 1024


def should_quantize(x):
    """True when a value is worth quantizing for the wire: a floating
    ndarray with at least :data:`WIRE_MIN_SIZE` elements."""
    return (isinstance(x, np.ndarray)
            and np.issubdtype(x.dtype, np.floating)
            and x.size >= WIRE_MIN_SIZE)


def wire_savings(qarr):
    """Bytes a quantized payload saves vs its f32 original (>= 0) —
    feeds the ``ps.rpc.bytes_saved`` counter on both push and pull."""
    orig = int(np.prod(qarr.shape, dtype=np.int64)) * 4
    return max(orig - qarr.nbytes, 0)


# --------------------------------------------------------------------- #
# jax half: traced encode/decode (comm ops + KV cache)
# --------------------------------------------------------------------- #

def quantize_jax(x, chunk=DEFAULT_CHUNK):
    """Traced twin of :func:`quantize_np` over the LAST axis: chunks of
    ``chunk`` trailing elements share a scale.  Returns (q int8 with
    x's shape, scales f32 with shape ``x.shape[:-1] + (n_chunks,)``).
    Requires the last dim to divide by ``chunk`` (callers pick chunk =
    a divisor; the comm pair flattens + pads first)."""
    chunk = int(chunk)
    *lead, last = x.shape
    if last % chunk:
        raise ValueError(
            f"last dim {last} not divisible by quant chunk {chunk}")
    g = x.astype(jnp.float32).reshape(*lead, last // chunk, chunk)
    amax = jnp.max(jnp.abs(g), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(g / scales[..., None]), -127, 127)
    return (q.astype(jnp.int8).reshape(x.shape), scales)


def dequantize_jax(q, scales, chunk=DEFAULT_CHUNK):
    """Inverse of :func:`quantize_jax` (f32, q's shape)."""
    chunk = int(chunk)
    *lead, last = q.shape
    g = q.astype(jnp.float32).reshape(*lead, last // chunk, chunk)
    return (g * scales[..., None]).reshape(q.shape)


def kv_encode(x):
    """KV-cache encode: one scale per (..., head) over the head_dim
    values of ``x`` [..., H, Dh] — fine-grained enough that greedy
    decode stays top-1-identical on the parity gates.  Returns
    (q int8 [..., H, Dh], scales f32 [..., H])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scales[..., None]),
                 -127, 127)
    return q.astype(jnp.int8), scales.astype(jnp.float32)


def kv_decode(q, scales):
    """Inverse of :func:`kv_encode` (f32)."""
    return q.astype(jnp.float32) * scales[..., None]

"""KV-segment wire format for disaggregated prefill/decode serving.

Disaggregation (DistServe/Mooncake-style) splits the two phases of a
generate request across replicas: a PREFILL replica computes the
prompt's KV rows, and a DECODE replica seats them and runs the
token loop — so long-prompt prefill bursts stop stealing decode TPOT
at the replica level. The hop between them is this module: one
self-describing binary frame carrying a prefix segment — exactly the
batch-1 slab the engine's ``_seg_fetch`` program produces (or its
paged block-list equivalent) plus the stored last-row logits — such
that decode seats it through the ordinary zero-prefill full-hit path.

The frame is deliberately dumb: a fixed magic + version + JSON header
(model-config hash, token ids, layout, per-leaf dtype/shape specs)
followed by the raw array bytes, concatenated in header order. No
compression, no chunking — dtype/shape round-trip EXACTNESS is the
contract (``tests/test_serving_disagg.py`` moves a segment through
``encode_segment``/``decode_segment`` and asserts the seated state is
bitwise identical to a local prefill), and raw bytes are the shortest
path to that. int8 segments ship their f32 scale planes as ordinary
leaves; bf16 ships as raw 2-byte words (``ml_dtypes`` round-trips the
dtype by name).

Receivers validate before touching a device: bad magic/version,
truncated or oversized payloads, and malformed headers raise
:class:`WireError` with HTTP status 400; a model-config-hash mismatch
(the segment was computed by a different checkpoint — seating it would
be silent corruption) raises with status 409. The HTTP layer maps
``WireError.status`` straight onto the response code, and senders fall
back to local prefill on any rejection — which is byte-identical
anyway, so a rejected transfer costs latency, never correctness.
"""

from __future__ import annotations

import hashlib
import json
import struct

import numpy as np

#: frame magic — first 4 bytes of every KV-segment frame
WIRE_MAGIC = b"KVSG"

#: wire format version; bumped on ANY header or payload layout change.
#: Receivers reject other versions outright (status 400) — a version
#: skew mid-rolling-restart must fall back to local prefill, never
#: misparse bytes into a cache. OPTIONAL header fields (like the
#: session-migration ``gen`` block) are additive and do NOT bump the
#: version: a v1 receiver that predates them never sees the endpoint
#: that sends them, and JSON headers ignore unknown keys by nature.
WIRE_VERSION = 1

_PREAMBLE = struct.Struct("<4sHI")  # magic, version, header length


class WireError(ValueError):
    """A KV-segment frame the receiver must not seat. ``status`` is
    the HTTP response code: 400 for malformed/truncated frames, 409
    for a model-config-hash mismatch (well-formed, wrong model)."""

    def __init__(self, message: str, status: int = 400):
        super().__init__(message)
        self.status = int(status)


def model_config_hash(cfg) -> str:
    """Stable identity of a model configuration: sha256 over the
    config's canonical JSON (``TransformerConfig.to_json``). Two
    engines agree on this hash iff they run the same architecture,
    dtypes and geometry — the precondition for a KV segment computed
    on one to be seatable on the other. (Weights are NOT hashed; the
    deployment contract is that replicas in one fleet serve one
    checkpoint, and the hash catches the config-level drift a rolling
    restart with the wrong model would introduce.)"""
    return hashlib.sha256(cfg.to_json().encode("utf-8")).hexdigest()


def _np_dtype(name: str) -> np.dtype:
    """dtype by name, including the ml_dtypes extension types (bf16
    etc.) numpy cannot look up by string on every version."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        try:
            return np.dtype(getattr(ml_dtypes, name))
        except AttributeError:
            raise WireError(f"unknown leaf dtype {name!r}") from None


def slab_to_blocks(leaves: list[np.ndarray],
                   block_size: int) -> list[np.ndarray]:
    """Reshape batch-1 slab leaves ``(L, C, 1, Tpad, H)`` into
    block-list leaves ``(L, C, Tpad/bs, bs, H)`` — the paged wire
    layout. Pure view-level reshape (rows are block-contiguous in the
    slab), zero copies beyond what ``tobytes`` does anyway."""
    out = []
    for a in leaves:
        L, C, one, tpad, H = a.shape
        if one != 1 or tpad % block_size:
            raise WireError(
                f"slab leaf {a.shape} not block-alignable at "
                f"block_size={block_size}"
            )
        out.append(a.reshape(L, C, tpad // block_size, block_size, H))
    return out


def blocks_to_slab(leaves: list[np.ndarray]) -> list[np.ndarray]:
    """Inverse of :func:`slab_to_blocks`: reassemble block-list leaves
    into the batch-1 slab form every seat path consumes."""
    out = []
    for a in leaves:
        L, C, nb, bs, H = a.shape
        out.append(a.reshape(L, C, 1, nb * bs, H))
    return out


def encode_segment(*, config_hash: str, tokens, leaves, logits,
                   layout: str = "slab", block_size: int = 0,
                   gen: dict | None = None) -> bytes:
    """Frame one prefix segment for the wire.

    ``leaves`` — the segment's cache arrays: batch-1 slab form
    ``(L, C, 1, Tpad, H)`` for ``layout="slab"``, block-list form
    ``(L, C, n_blocks, block_size, H)`` for ``layout="paged"`` (use
    :func:`slab_to_blocks`). ``logits`` — the stored ``(1, V)``
    last-row logits that make the seated segment full-hit capable.
    Arrays are framed as raw bytes in C order; dtype and shape ride
    the header, so the round-trip is exact for every dtype the engine
    pools (bf16, f32, int8 + f32 scale planes alike).

    ``gen`` — optional LIVE-SESSION state for migration frames: a
    JSON-able dict carrying the generating request's identity and
    mid-generation position (prompt length, tokens emitted so far,
    remaining budget, sampling-key words). Plain-segment frames omit
    it; receivers that don't understand it never see it (additive
    header field, see :data:`WIRE_VERSION`).
    """
    if layout not in ("slab", "paged"):
        raise WireError(f"unknown layout {layout!r}")
    if layout == "paged" and int(block_size) <= 0:
        raise WireError("paged layout requires a positive block_size")
    arrs = [np.ascontiguousarray(a) for a in leaves]
    lg = np.ascontiguousarray(logits)
    header = {
        "version": WIRE_VERSION,
        "config_hash": str(config_hash),
        "layout": layout,
        "block_size": int(block_size),
        "tokens": [int(t) for t in np.asarray(tokens).reshape(-1)],
        "leaves": [
            {"dtype": a.dtype.name, "shape": list(a.shape)} for a in arrs
        ],
        "logits": {"dtype": lg.dtype.name, "shape": list(lg.shape)},
    }
    if gen is not None:
        header["gen"] = dict(gen)
    hjson = json.dumps(header, sort_keys=True).encode("utf-8")
    parts = [_PREAMBLE.pack(WIRE_MAGIC, WIRE_VERSION, len(hjson)), hjson]
    parts += [a.tobytes() for a in arrs]
    parts.append(lg.tobytes())
    return b"".join(parts)


def _read_array(data: bytes, spec: dict, off: int,
                what: str) -> tuple[np.ndarray, int]:
    try:
        dt = _np_dtype(str(spec["dtype"]))
        shape = tuple(int(d) for d in spec["shape"])
    except (KeyError, TypeError, ValueError):
        raise WireError(f"malformed {what} spec {spec!r}") from None
    count = 1
    for d in shape:
        if d < 0:
            raise WireError(f"negative dimension in {what} spec")
        count *= d
    nbytes = count * dt.itemsize
    if off + nbytes > len(data):
        raise WireError(
            f"truncated payload: {what} needs {nbytes} bytes at "
            f"offset {off}, frame has {len(data)}"
        )
    arr = np.frombuffer(data, dt, count=count, offset=off).reshape(shape)
    return arr, off + nbytes


def decode_segment(data: bytes, *,
                   expect_hash: str | None = None) -> dict:
    """Parse and validate one wire frame; the inverse of
    :func:`encode_segment`.

    Returns ``{"config_hash", "layout", "block_size", "tokens"
    (int32 array), "leaves" (batch-1 SLAB-form arrays — paged frames
    are reassembled), "logits", "gen" (the optional live-session
    block, ``None`` for plain segments), "nbytes"}``. Raises
    :class:`WireError`
    (status 400) on bad magic/version, malformed headers, or payloads
    whose byte count disagrees with the declared specs, and (status
    409) when ``expect_hash`` is given and the frame's config hash
    differs — the caller must fall back to local prefill, not seat a
    foreign checkpoint's KV.
    """
    if len(data) < _PREAMBLE.size:
        raise WireError("frame shorter than preamble")
    magic, version, hlen = _PREAMBLE.unpack_from(data)
    if magic != WIRE_MAGIC:
        raise WireError(f"bad magic {magic!r}")
    if version != WIRE_VERSION:
        raise WireError(
            f"unsupported wire version {version} "
            f"(speaking {WIRE_VERSION})"
        )
    if _PREAMBLE.size + hlen > len(data):
        raise WireError("truncated header")
    try:
        header = json.loads(
            data[_PREAMBLE.size:_PREAMBLE.size + hlen].decode("utf-8")
        )
    except (UnicodeDecodeError, json.JSONDecodeError):
        raise WireError("malformed header JSON") from None
    try:
        config_hash = str(header["config_hash"])
        layout = str(header["layout"])
        block_size = int(header["block_size"])
        tokens = np.asarray(
            [int(t) for t in header["tokens"]], np.int32
        )
        leaf_specs = list(header["leaves"])
        logit_spec = dict(header["logits"])
    except (KeyError, TypeError, ValueError):
        raise WireError("header missing required fields") from None
    gen = header.get("gen")
    if gen is not None and not isinstance(gen, dict):
        raise WireError("gen header field must be an object")
    if layout not in ("slab", "paged"):
        raise WireError(f"unknown layout {layout!r}")
    if expect_hash is not None and config_hash != expect_hash:
        raise WireError(
            f"model config hash mismatch: frame {config_hash[:12]}..., "
            f"receiver {expect_hash[:12]}...",
            status=409,
        )
    off = _PREAMBLE.size + hlen
    leaves = []
    for i, spec in enumerate(leaf_specs):
        arr, off = _read_array(data, spec, off, f"leaf {i}")
        leaves.append(arr)
    logits, off = _read_array(data, logit_spec, off, "logits")
    if off != len(data):
        raise WireError(
            f"{len(data) - off} trailing bytes after declared payload"
        )
    if layout == "paged":
        if block_size <= 0:
            raise WireError("paged frame with non-positive block_size")
        leaves = blocks_to_slab(leaves)
    return {
        "config_hash": config_hash,
        "layout": layout,
        "block_size": block_size,
        "tokens": tokens,
        "leaves": leaves,
        "logits": logits,
        "gen": gen,
        "nbytes": len(data),
    }

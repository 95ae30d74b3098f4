"""KV wire format: serialize filled block-table rows for cross-process
transfer.

A *shipment* carries, for one request prefix, the paged-KV pool rows
that hold its already-prefilled tokens (every pool the model has, as
its step lists them: K and V a layer, or one latent pool a layer), plus the content
chain hashes (`PrefixCache.chunk_hashes`) that name them and the
start-position metadata a decode worker needs to resume.  Payloads are
base64 of the raw pool bytes — `np.tobytes`/`np.frombuffer` round-trip
is byte-exact for fp32 and bf16 alike, so the adopting worker decodes
from tensors bit-identical to the ones the prefill worker computed.

The format rides the existing serving/http.py JSON protocol (one JSON
object per POST body); no new transport is introduced.
"""
from __future__ import annotations

import base64
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

WIRE_VERSION = 3   # 3: a flat list of pools, each [n_blocks, block_size, lanes]


def _resolve_dtype(name: str) -> np.dtype:
    """Resolve a dtype name from the wire, including bfloat16 (which
    numpy alone does not know — jax ships ml_dtypes, so gate on it)."""
    if name == "bfloat16":
        try:
            import ml_dtypes  # noqa: F401  (registers bfloat16)
            return np.dtype(ml_dtypes.bfloat16)
        except ImportError as e:  # pragma: no cover - env without jax
            raise ValueError(
                "shipment dtype bfloat16 needs ml_dtypes "
                "(bundled with jax)") from e
    return np.dtype(name)


def _dtype_name(dt: np.dtype) -> str:
    return dt.name


@dataclass
class KVShipment:
    """Decoded wire payload: a row stack of shape [n_blocks,
    block_size, lanes] for each pool of the model, in its step's
    `cache_names` order (a pool's rows as the pool holds them:
    ops/attention.py:paged_attention)."""
    version: int
    block_size: int
    n_tokens: int
    dtype: np.dtype
    shape: Tuple[int, int, int]
    chain_hashes: List[str]
    pools: List[np.ndarray]

    @property
    def n_blocks(self) -> int:
        return self.shape[0]


def pack_blocks(scope, cache_names: Sequence[str],
                block_ids: Sequence[int],
                chain_hashes: Sequence[str],
                block_size: int,
                state_names: Sequence[str] = ()) -> dict:
    """Serialize pool rows `block_ids` from every paged KV pool in
    `cache_names` (the model's list as it is) into a JSON-safe dict.

    `chain_hashes[i]` must be the content hash of the tokens stored in
    `block_ids[i]`; the adopting side keys its PrefixCache on them.
    `state_names` are the step's recurrent state variables: a model
    that has any is refused, because the format ships KV blocks only.
    """
    if state_names:
        raise ValueError(
            f"kv_wire ships paged KV blocks only; this model also "
            f"carries per-slot recurrent state ({state_names[0]}, ...) "
            f"that a block does not hold")
    if len(block_ids) != len(chain_hashes):
        raise ValueError(
            f"{len(block_ids)} block ids vs {len(chain_hashes)} hashes")
    ids = list(int(b) for b in block_ids)
    pools = []
    shape = None
    dtype = None
    for name in cache_names:
        pool = np.asarray(scope.get(name))
        rows = np.ascontiguousarray(pool[ids])
        if shape is None:
            shape = rows.shape
            dtype = rows.dtype
        elif (rows.shape, rows.dtype) != (shape, dtype):
            raise ValueError(
                f"{name}: rows {rows.dtype}{list(rows.shape)} beside "
                f"{dtype}{list(shape)}: one shipment, one row shape")
        pools.append(base64.b64encode(rows.tobytes()).decode("ascii"))
    if shape is None:
        shape = (len(ids), block_size, 0)
        dtype = np.dtype("float32")
    payload = {
        "kind": "kv_shipment",
        "version": WIRE_VERSION,
        "block_size": int(block_size),
        "n_blocks": len(ids),
        "n_tokens": len(ids) * int(block_size),
        "dtype": _dtype_name(dtype),
        "shape": [int(d) for d in shape],
        "chain_hashes": list(chain_hashes),
        "pools": pools,
    }
    return payload


def unpack_blocks(payload: dict) -> KVShipment:
    """Decode a `pack_blocks` dict back into numpy row stacks.

    Raises ValueError on malformed payloads (wrong kind/version,
    truncated buffers) so http.py can map it to a 400.
    """
    if payload.get("kind") != "kv_shipment":
        raise ValueError("not a kv_shipment payload")
    if payload.get("version") != WIRE_VERSION:
        raise ValueError(
            f"kv_shipment version {payload.get('version')!r}, "
            f"expected {WIRE_VERSION}")
    shape = tuple(int(d) for d in payload["shape"])
    if len(shape) != 3:
        raise ValueError(f"bad shipment shape {shape}")
    dtype = _resolve_dtype(str(payload["dtype"]))
    hashes = [str(h) for h in payload["chain_hashes"]]
    if len(hashes) != shape[0]:
        raise ValueError(
            f"{len(hashes)} chain hashes for {shape[0]} blocks")
    want = int(np.prod(shape)) * dtype.itemsize
    pools: List[np.ndarray] = []
    for i, coded in enumerate(payload["pools"]):
        raw = base64.b64decode(coded)
        if len(raw) != want:
            raise ValueError(
                f"pool {i} buffer is {len(raw)} bytes, expected {want}")
        pools.append(np.frombuffer(raw, dtype=dtype).reshape(shape))
    return KVShipment(
        version=WIRE_VERSION,
        block_size=int(payload["block_size"]),
        n_tokens=int(payload["n_tokens"]),
        dtype=dtype,
        shape=shape,  # type: ignore[arg-type]
        chain_hashes=hashes,
        pools=pools)


def payload_bytes(payload: dict) -> int:
    """Raw KV bytes carried by a packed shipment (excludes base64 and
    JSON overhead): pools * prod(shape) * itemsize."""
    shape = [int(d) for d in payload.get("shape", ())]
    if len(shape) != 3:
        return 0
    dtype = _resolve_dtype(str(payload.get("dtype", "float32")))
    per_pool = int(np.prod(shape)) * dtype.itemsize
    return per_pool * len(payload.get("pools", ()))

"""Paged KV cache: device-side page pools + host-side block allocator.

The north star's core memory structure (no reference analog — the reference
is stateless; SURVEY.md §2b "Paged KV cache"): KV for all sequences lives in
fixed-size pages inside one preallocated pool per layer, so sequences grow
without reallocation or fragmentation, and the decode batch is composed by
page-table indirection rather than copying.

Stored layout, stated here once and pointed to elsewhere: ONE array
[num_layers, num_pages, 2, page_size, num_kv_heads · head_dim] — K at index
0 and V at index 1 of a page, heads FOLDED into the last (lane) dimension,
head-major, so a `tp` shard of that dimension is whole heads and one page is
a contiguous, 128-lane-aligned [2, page_size, Hk·D] slab: what both Pallas
kernels DMA under ONE descriptor a page (a descriptor costs ~10 ns on a v5e
whatever it carries, as much as the bytes of a tp shard's 8 KB page half: K
and V pools apart paid it twice a page — PERF.md §6, PR 46), with no
reshape of a pool anywhere (under the TPU's tiled layout splitting the last
dimension is a relayout of the whole pool, not a bitcast). The model step
views the stack as its page halves, [L·N·2, page_size, Hk·D] (a merge of
leading dimensions only: page p's K at 2p, its V at 2p + 1 — the kernels
take both under one descriptor, the XLA gathers and scatters each by one
index), and addresses page (layer, page) as `layer · N + page` —
models/transformer.py `_run_paged_stack`. int8-KV scale pools stay two,
[L, N, page_size, Hk] each. The host tier (HostKVPool) and the handoff wire
format (KVHandoffState) keep K and V in arrays of their own with the heads
apart, [..., Hk, D]; `fold_pages` / `unfold_pages` convert page-sized host
arrays at that boundary, so no byte of a host page or a wire blob follows
the device's layout.

A page's PARTS are a fact of the model (ModelConfig.kv_parts,
kv_row_width): K and V, two parts of Hk·D columns, as above — or, for a
latent-attention pattern ("A" layers, models/hybrid.py), ONE part: a page
is [page_size, W], a token's single latent row (its normed latent beside
the one rotary key all heads share, zero columns up to whole 128-lane
tiles: W = 640 for the published 512 + 64), the array
[num_layers, num_pages, 1, page_size, W], carried as [L·N, page_size, W]
with page p at entry p. No per-head K or V of such a model is ever stored:
the write paths take the one row, the decode kernel reads a page once for
all heads (ops/paged_attention_kernel.py `mla_latent_decode`). The host
tier and the wire format know two-part pages only, and a latent pool is
refused with them (engine/config.py `_refuse_for_latent_pool`).

The allocator is host-side bookkeeping: a refcounted free list.
Page 0 is reserved as the garbage page — inactive decode
slots point at it so masked lanes always have a safe write target.
"""

from __future__ import annotations

import json
import struct as _struct
import zlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..models.config import ModelConfig


class AllocationError(RuntimeError):
    """Not enough free pages for the request (admission should back off)."""


class BlockAllocator:
    """Refcounted free-list page allocator."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self._refcount = [0] * num_pages
        self._refcount[0] = 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, count: int) -> list[int]:
        """Allocate `count` pages; all-or-nothing."""
        if count == 0:
            return []
        if len(self._free) < count:
            raise AllocationError(
                f"requested {count} pages, {len(self._free)} free"
            )
        pages = [self._free.pop() for _ in range(count)]
        for p in pages:
            self._refcount[p] = 1
        return pages

    def retain(self, page: int) -> None:
        if page <= 0 or page >= self.num_pages or self._refcount[page] == 0:
            raise ValueError(f"retain of unallocated page {page}")
        self._refcount[page] += 1

    def release(self, page: int) -> None:
        if page <= 0 or page >= self.num_pages or self._refcount[page] == 0:
            raise ValueError(f"release of unallocated page {page}")
        self._refcount[page] -= 1
        if self._refcount[page] == 0:
            self._free.append(page)

    def release_all(self, pages: list[int]) -> None:
        for p in pages:
            self.release(p)


@struct.dataclass
class PagedKV:
    """Device-side page pool: kv [L, num_pages, parts, page_size, W] — K
    and V of a page side by side, heads folded into lanes (parts 2,
    W = Hk·D), or one latent row a token (parts 1): the stored layout,
    module docstring.

    With int8 KV (EngineConfig.kv_dtype="int8") kv holds int8 values and
    ks/vs hold per-(token, head) bf16 scales [L, num_pages, page_size, Hk]
    — symmetric absmax over the head_dim axis, quantized at write time
    (ops/paged_attention.paged_write) and dequantized at read time. The
    scale overhead is 1/(2·D) of the bf16 pool (~0.4% at D=128); the pool
    itself halves, which is the slot-count lever on a 16 GiB chip.
    ks/vs are None for fp pools (an empty pytree subtree — the fp paths
    never see extra buffers)."""

    kv: jax.Array
    ks: Optional[jax.Array] = None
    vs: Optional[jax.Array] = None

    @property
    def page_size(self) -> int:
        return self.kv.shape[3]

    @property
    def num_pages(self) -> int:
        return self.kv.shape[1]

    @property
    def quantized(self) -> bool:
        return self.ks is not None


@struct.dataclass
class SlotState:
    """What a slot holds BESIDE its pages: the fixed-size state of a
    stateful model (ModelConfig.stateful), indexed by slot, one entry per
    layer that needs it, in pattern order. `ssm`, the recurrent matrix of
    a layer, one per layer that has a recurrence — a Mamba-2 mixer's h
    [slots, H, P, N], a gated delta rule's S [slots, Hv ÷ n, Dk, n · Dv]
    (n = ModelConfig.delta_heads_per_row value heads side by side, so
    that a row is whole 128-lane tiles where the heads allow it;
    ops/hybrid_kernels.py `pack_heads`) — in
    float32 (the recurrence is summed over thousands of steps). `conv`,
    one per layer with a causal conv — a mixer, a delta-rule layer or a
    gated short convolution: [slots, K−1, channels] in the activation
    dtype (the conv's last K−1 input columns, stored as they were
    computed). A tuple is empty where the pattern has no such layer (a
    conv-only model holds
    no `ssm` leaf), and a model without such state holds two empty tuples
    — an empty pytree: nothing is allocated, carried or donated for it.

    It rides every dispatch that `PagedKV` rides, donated the same way,
    so the donation chain orders its writers as it orders the pool's.
    The rules its writers keep (models/hybrid.py; tests/test_hybrid.py):
    a prompt's first window starts from zero state, whatever the slot's
    last occupant left; a padded position never advances it, and what is
    stored is the state after the last REAL token; an inactive decode
    lane's state is not advanced; a prompt's next window in the same
    dispatch starts where the row above ended; a long prompt's next chunk
    starts from what the slot stores."""

    ssm: tuple = ()
    conv: tuple = ()

    @property
    def nbytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self))

    @property
    def resident_nbytes(self) -> int:
        """Bytes the leaves take on their device (`resident_nbytes`)."""
        return sum(resident_nbytes(x) for x in jax.tree.leaves(self))


def resident_nbytes(x: jax.Array) -> int:
    """Bytes `x` takes where it lives: its minor dims rounded up to the
    tile of the layout the device holds it in (a TPU holds a float32
    matrix in tiles of 8 × 128, so a last dim of 192 takes 256), read from
    the array's own layout. Where the layout has no tiles (the CPU), the
    array's nominal bytes."""
    layout = x.format.layout
    if not layout.tiling:
        return x.nbytes
    dims, tile = list(x.shape), layout.tiling[0]
    for axis, t in zip(layout.major_to_minor[-len(tile):], tile):
        dims[axis] = -(-dims[axis] // t) * t
    return int(np.prod(dims)) * x.dtype.itemsize


def init_slot_state(cfg: ModelConfig, slots: int, dtype=jnp.bfloat16) -> SlotState:
    per_row = cfg.delta_heads_per_row
    matrix = {
        "M": (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
        "L": (cfg.delta_value_heads // per_row, cfg.delta_key_dim,
              per_row * cfg.delta_value_dim),
    }
    channels = {"M": cfg.conv_dim, "C": cfg.hidden_size,
                "L": cfg.delta_conv_dim}
    return SlotState(
        ssm=tuple(jnp.zeros((slots, *matrix[ch]), jnp.float32)
                  for ch in cfg.layer_pattern if ch in matrix),
        conv=tuple(
            jnp.zeros((slots, max(cfg.conv_kernel - 1, 0), channels[ch]), dtype)
            for ch in cfg.layer_pattern if ch in channels),
    )


def init_paged_kv(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    kv_dtype=None,
) -> PagedKV:
    """`kv_dtype=jnp.int8` builds quantized pools (+ bf16 scale pools);
    None keeps the full-precision layout in `dtype`. One pool layer for
    each layer that attends (a layer pattern's "*" or "A" layers); a
    page's parts and a row's width are the model's (ModelConfig.kv_parts,
    kv_row_width: K and V, or one latent row)."""
    shape = (cfg.kv_layers, num_pages, cfg.kv_parts, page_size,
             cfg.kv_row_width)
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        if cfg.latent_kv:
            raise ValueError("a latent pool has no int8 form")
        sshape = (cfg.kv_layers, num_pages, page_size, cfg.num_kv_heads)
        return PagedKV(
            kv=jnp.zeros(shape, jnp.int8),
            ks=jnp.zeros(sshape, jnp.bfloat16),
            vs=jnp.zeros(sshape, jnp.bfloat16),
        )
    return PagedKV(kv=jnp.zeros(shape, dtype))


def fold_heads(pages):
    """[..., Hk, D] → [..., Hk·D]: rows or pages with the heads apart into
    the stored lane fold (a view on a contiguous numpy array)."""
    return pages.reshape(*pages.shape[:-2], -1)


def unfold_heads(pages, head_dim: int):
    """[..., Hk·D] → [..., Hk, D]: the inverse of `fold_heads`."""
    return pages.reshape(*pages.shape[:-1], -1, head_dim)


def fold_pages(k: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Host-tier / wire pages, K and V apart [..., page_size, Hk, D], into
    the stored layout [..., 2, page_size, Hk·D] for an upload."""
    return np.stack([fold_heads(k), fold_heads(v)], axis=-3)


def unfold_pages(kv: np.ndarray, head_dim: int) -> tuple:
    """Gathered pool pages [..., 2, page_size, Hk·D] into the host-tier /
    wire layout: (k, v), each [..., page_size, Hk, D]."""
    pages = unfold_heads(kv, head_dim)
    return pages[..., 0, :, :, :], pages[..., 1, :, :, :]


def kv_pool_bytes(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    kv_dtype=None,
) -> int:
    """Bytes of the pool `init_paged_kv` allocates: every part of every
    page of every pool layer (int8: values and a bf16 scale a head)."""
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        per_part = cfg.num_kv_heads * (cfg.head_dim * 1 + 2)  # values + scale
    else:
        per_part = cfg.kv_row_width * jnp.dtype(dtype).itemsize
    return cfg.kv_parts * cfg.kv_layers * num_pages * page_size * per_part


def host_kv_page_bytes(
    cfg: ModelConfig, page_size: int, dtype=jnp.bfloat16, kv_dtype=None,
) -> int:
    """Bytes ONE page occupies in the host tier (a page's parts across all
    layers, plus the bf16 scale rows for int8 pools) — the unit
    POLYKEY_HOST_KV_BYTES divides into a page capacity."""
    return kv_pool_bytes(cfg, 1, page_size, dtype, kv_dtype)


class HostKVPool:
    """Second KV tier in host RAM (ISSUE 15): preallocated numpy pools
    of pages with the heads kept apart — k/v [L, capacity, page_size,
    Hk, D] (+ ks/vs scale pools [L, capacity, page_size, Hk] for int8)
    — holding COLD pages spilled from the device pool by the prefix
    cache. Pages here are never computed against: they exist to be
    scattered back into the device pool (`engine._jit_kv_restore`) when
    a prefix-cache lookup hits a spilled entry, so max cold capacity
    bounds on host RAM instead of HBM.

    Preallocation is deliberate: one contiguous buffer per pool at
    construction (the CPU analog of pinned host memory — on TPU hosts
    these become the staging buffers DMA engines copy from), no
    allocation on the spill/restore paths, and the capacity check is
    one free-list pop. Single-owner: only the engine thread touches it.
    """

    def __init__(self, cfg: ModelConfig, capacity_pages: int,
                 page_size: int, dtype, quantized: bool):
        if capacity_pages < 1:
            raise ValueError("HostKVPool needs capacity_pages >= 1")
        self.capacity = capacity_pages
        shape = (cfg.num_layers, capacity_pages, page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        if quantized:
            self.k = np.zeros(shape, np.int8)
            self.v = np.zeros(shape, np.int8)
            self.ks = np.zeros(shape[:-1], jnp.dtype(jnp.bfloat16))
            self.vs = np.zeros(shape[:-1], jnp.dtype(jnp.bfloat16))
        else:
            self.k = np.zeros(shape, jnp.dtype(dtype))
            self.v = np.zeros(shape, jnp.dtype(dtype))
            self.ks = None
            self.vs = None
        self._free = list(range(capacity_pages - 1, -1, -1))

    @property
    def quantized(self) -> bool:
        return self.ks is not None

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        """One host page; AllocationError when the tier is full — the
        caller's LRU pressure policy decides what to drop."""
        if not self._free:
            raise AllocationError(
                f"host KV tier full ({self.capacity} pages)"
            )
        return self._free.pop()

    def release(self, page: int) -> None:
        if page < 0 or page >= self.capacity:
            raise ValueError(f"release of invalid host page {page}")
        self._free.append(page)

    def write(self, page: int, k: np.ndarray, v: np.ndarray,
              ks: Optional[np.ndarray] = None,
              vs: Optional[np.ndarray] = None) -> None:
        """Copy one page's contents ([L, page_size, Hk, D] slices of a
        gather result) into the host buffers — raw bytes, no dtype
        conversion, so a later restore is bit-identical."""
        self.k[:, page] = k
        self.v[:, page] = v
        if self.quantized:
            self.ks[:, page] = ks
            self.vs[:, page] = vs

    def read(self, page: int) -> tuple:
        """(k, v, ks, vs) views of one host page (restore operands are
        built by copying these into the padded upload buffer)."""
        if self.quantized:
            return (self.k[:, page], self.v[:, page],
                    self.ks[:, page], self.vs[:, page])
        return self.k[:, page], self.v[:, page], None, None


# -- KV handoff wire format (ISSUE 13) ----------------------------------------
# A prefill-tier worker ships a finished prompt's KV state to a
# decode-tier worker as one self-describing byte blob: gathered page
# contents (k/v, plus the int8 pair-form scale pools when quantized),
# the block-table ordering (implicit: pages ship in table order and the
# target re-maps them to its own page ids), and the prefix/prompt
# metadata the target needs to resume decode bit-identically (prompt
# ids, first sampled token, RNG seed). Everything is raw array bytes —
# no dtype conversion anywhere — so fp32 and int8 pools round-trip
# bit-identically; bf16 rides ml_dtypes through numpy unchanged.
#
# Layout:  MAGIC(4) | version u16 | header_len u32 | header JSON |
#          payload bytes | crc32(payload) u32
# The header's `arrays` table records each array's dtype/shape/offset
# within the payload. A truncated blob fails the length check (or the
# trailing CRC) and raises KVWireError — a typed, recoverable rejection
# the coordinator turns into a clean re-route instead of a corrupted
# target pool.

KV_WIRE_MAGIC = b"PKKV"
KV_WIRE_VERSION = 1


class KVWireError(RuntimeError):
    """The handoff blob cannot be (safely) applied: bad magic/version,
    geometry mismatch against the target pool, or a truncated/corrupt
    payload. Always raised BEFORE any target-pool write, so a rejected
    handoff never leaves partial state behind."""


@dataclass
class KVHandoffState:
    """One request's prefill-complete KV state, host-side.

    Arrays hold this request's pages in block-table order with the heads
    kept apart (the wire format; it did not move when the device pools
    were folded): k/v are [L, n_pages, page_size, Hk, D]; ks/vs (int8 pools only) are
    [L, n_pages, page_size, Hk]. `prompt_ids` is the tokenized (and
    possibly tail-truncated) prompt — positions 0..prompt_len-1 are the
    ones the pages hold KV for. `first_token` was sampled at position
    key prompt_len with `seed`, exactly as a single-process prefill
    would; the target resumes decode at seq_len = prompt_len + 1."""

    model: str
    page_size: int
    prompt_len: int
    first_token: int
    seed: int
    prompt_ids: np.ndarray
    k: np.ndarray
    v: np.ndarray
    ks: Optional[np.ndarray] = None
    vs: Optional[np.ndarray] = None

    @property
    def num_pages(self) -> int:
        return int(self.k.shape[1])

    @property
    def quantized(self) -> bool:
        return self.ks is not None

    def validate_for(self, cfg: ModelConfig, page_size: int,
                     quantized: bool) -> None:
        """Raise KVWireError unless this state fits the target pool's
        geometry exactly — the guard that keeps a mismatched handoff a
        typed rejection instead of silent pool corruption."""
        expect = (cfg.num_layers, self.num_pages, page_size,
                  cfg.num_kv_heads, cfg.head_dim)
        if self.model != cfg.name:
            raise KVWireError(
                f"kv-handoff model mismatch: blob for {self.model!r}, "
                f"target serves {cfg.name!r}"
            )
        if self.page_size != page_size:
            raise KVWireError(
                f"kv-handoff page_size mismatch: blob {self.page_size}, "
                f"target pool {page_size}"
            )
        if tuple(self.k.shape) != expect or tuple(self.v.shape) != expect:
            raise KVWireError(
                f"kv-handoff geometry mismatch: pages {self.k.shape} vs "
                f"target {expect}"
            )
        if quantized != self.quantized:
            raise KVWireError(
                "kv-handoff dtype mismatch: blob is "
                f"{'int8' if self.quantized else 'full-precision'}, target "
                f"pool is {'int8' if quantized else 'full-precision'}"
            )
        needed = -(-self.prompt_len // page_size)
        if self.num_pages != needed:
            raise KVWireError(
                f"kv-handoff page count {self.num_pages} does not cover "
                f"prompt_len {self.prompt_len} (need {needed})"
            )


def _array_entries(state: KVHandoffState) -> list[tuple[str, np.ndarray]]:
    entries = [
        ("prompt_ids", np.ascontiguousarray(state.prompt_ids, np.int32)),
        ("k", np.ascontiguousarray(state.k)),
        ("v", np.ascontiguousarray(state.v)),
    ]
    if state.ks is not None:
        entries.append(("ks", np.ascontiguousarray(state.ks)))
        entries.append(("vs", np.ascontiguousarray(state.vs)))
    return entries


def serialize_kv_state(state: KVHandoffState) -> bytes:
    """Render a KVHandoffState as one wire blob (see module comment)."""
    entries = _array_entries(state)
    arrays = []
    payload_parts = []
    offset = 0
    for name, arr in entries:
        raw = arr.tobytes()
        arrays.append({
            "name": name,
            # jnp.dtype resolves ml_dtypes names (bfloat16) that plain
            # numpy's dtype constructor does not.
            "dtype": str(arr.dtype),
            "shape": list(arr.shape),
            "offset": offset,
            "nbytes": len(raw),
        })
        payload_parts.append(raw)
        offset += len(raw)
    payload = b"".join(payload_parts)
    header = json.dumps({
        "model": state.model,
        "page_size": state.page_size,
        "prompt_len": state.prompt_len,
        "first_token": int(state.first_token),
        "seed": int(state.seed),
        "quantized": state.quantized,
        "arrays": arrays,
        "payload_bytes": len(payload),
    }).encode()
    return b"".join([
        KV_WIRE_MAGIC,
        _struct.pack("!HI", KV_WIRE_VERSION, len(header)),
        header,
        payload,
        _struct.pack("!I", zlib.crc32(payload) & 0xFFFFFFFF),
    ])


def _parse_header(buf: bytes) -> tuple[dict, int]:
    """(header dict, payload start offset); raises KVWireError on a blob
    too short or malformed to even carry a header."""
    head = len(KV_WIRE_MAGIC) + 6
    if len(buf) < head:
        raise KVWireError(
            f"kv-handoff blob truncated: {len(buf)} bytes is shorter than "
            "the fixed header"
        )
    if buf[:4] != KV_WIRE_MAGIC:
        raise KVWireError(
            f"kv-handoff bad magic {buf[:4]!r} (expected {KV_WIRE_MAGIC!r})"
        )
    version, header_len = _struct.unpack("!HI", buf[4:head])
    if version != KV_WIRE_VERSION:
        raise KVWireError(
            f"kv-handoff version {version} unsupported (this build speaks "
            f"{KV_WIRE_VERSION})"
        )
    if len(buf) < head + header_len:
        raise KVWireError("kv-handoff blob truncated inside the header")
    try:
        header = json.loads(buf[head:head + header_len])
    except ValueError as e:
        raise KVWireError(f"kv-handoff header unparsable: {e}") from e
    return header, head + header_len


def validate_kv_blob(buf: bytes) -> dict:
    """Light structural validation (header + framing + CRC) WITHOUT
    materializing arrays — what the coordinator runs on a fetched blob
    before paying a ship to the decode tier. Returns the header dict;
    raises KVWireError on any truncation/corruption."""
    header, start = _parse_header(buf)
    payload_bytes = int(header.get("payload_bytes", -1))
    expected = start + payload_bytes + 4
    if payload_bytes < 0 or len(buf) < expected:
        raise KVWireError(
            f"kv-handoff blob truncated: have {len(buf)} bytes, framing "
            f"declares {expected} (partial write?)"
        )
    payload = buf[start:start + payload_bytes]
    (crc,) = _struct.unpack(
        "!I", buf[start + payload_bytes:start + payload_bytes + 4]
    )
    if crc != (zlib.crc32(payload) & 0xFFFFFFFF):
        raise KVWireError("kv-handoff payload CRC mismatch (corrupt blob)")
    return header


def deserialize_kv_state(buf: bytes) -> KVHandoffState:
    """Parse a wire blob back into a KVHandoffState, bit-identically
    (raw-byte round-trip, no dtype conversion). Raises KVWireError on
    bad magic/version, truncation, or CRC mismatch — never applies a
    partial blob."""
    header = validate_kv_blob(buf)
    _, start = _parse_header(buf)
    payload = buf[start:start + int(header["payload_bytes"])]
    arrays: dict[str, np.ndarray] = {}
    for entry in header["arrays"]:
        raw = payload[entry["offset"]:entry["offset"] + entry["nbytes"]]
        if len(raw) != entry["nbytes"]:
            raise KVWireError(
                f"kv-handoff array {entry['name']!r} truncated"
            )
        arr = np.frombuffer(
            raw, dtype=jnp.dtype(entry["dtype"])
        ).reshape(entry["shape"])
        arrays[entry["name"]] = arr
    for required in ("prompt_ids", "k", "v"):
        if required not in arrays:
            raise KVWireError(f"kv-handoff blob missing array {required!r}")
    # The header's `quantized` flag must agree with the arrays actually
    # shipped — a mismatch means the serializer and this reader disagree
    # about the pool form, and applying the blob would mix int8 values
    # with a full-precision target (racelint CL005 pins this field as
    # read-back on both sides).
    if bool(header.get("quantized")) != ("ks" in arrays):
        raise KVWireError(
            "kv-handoff header/payload mismatch: quantized="
            f"{bool(header.get('quantized'))} but scale pools are "
            f"{'present' if 'ks' in arrays else 'absent'}"
        )
    return KVHandoffState(
        model=header["model"],
        page_size=int(header["page_size"]),
        prompt_len=int(header["prompt_len"]),
        first_token=int(header["first_token"]),
        seed=int(header["seed"]),
        prompt_ids=arrays["prompt_ids"],
        k=arrays["k"],
        v=arrays["v"],
        ks=arrays.get("ks"),
        vs=arrays.get("vs"),
    )

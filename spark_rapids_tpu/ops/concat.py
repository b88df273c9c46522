"""Batch concatenation kernels.

Reference analog: cudf ``Table.concatenate`` as used by GpuCoalesceBatches
(GpuCoalesceBatches.scala:398-571) and GpuShuffleCoalesceExec. Lengths are
host ints at batch boundaries (the reference syncs for row counts there
too), so each part placement is a static ``dynamic_update_slice`` and XLA
fuses the whole stitch into one program.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..columnar.column import choose_capacity
from ..expr.eval import ColV, DictV, StrV, Val


def live_prefix(vals: Sequence[Val], live_cap: int) -> List[Val]:
    """The first ``live_cap`` slots of every plane of a batch whose live
    rows (a dense prefix, by the batch's contract) fit that bucket: a
    batch that holds fewer rows than it was given slots (a ``PARTIAL``
    aggregate's 100 groups at the capacity of its stacked row groups). A
    static slice, so no gather; a string keeps its byte pool, a dictionary
    column its dictionary, with the byte bound of its expansion cut to what
    ``live_cap`` rows can hold. Trace-safe."""
    out: List[Val] = []
    for v in vals:
        if isinstance(v, StrV):
            out.append(StrV(v.offsets[:live_cap + 1], v.chars,
                            v.validity[:live_cap]))
        elif isinstance(v, DictV):
            out.append(DictV(
                v.codes[:live_cap], v.dictionary,
                v.validity[:live_cap],
                min(v.mat_cap, choose_capacity(
                    max(1, live_cap * v.max_len), 128)),
                v.max_len, v.unique))
        else:
            out.append(ColV(v.data[:live_cap],
                            v.validity[:live_cap]))
    return out


def concat_fixed(parts: Sequence[ColV], lengths: Sequence[int], out_cap: int) -> ColV:
    dtype = parts[0].data.dtype
    data = jnp.zeros(out_cap, dtype)
    validity = jnp.zeros(out_cap, jnp.bool_)
    off = 0
    for p, n in zip(parts, lengths):
        if n == 0:
            continue
        data = lax.dynamic_update_slice(data, p.data[:n], (off,))
        validity = lax.dynamic_update_slice(validity, p.validity[:n], (off,))
        off += n
    return ColV(data, validity)


def concat_padded_cols(
    col_parts: Sequence[Sequence[ColV]],
    counts: Sequence[jax.Array],
    out_cap: int,
) -> Tuple[List[ColV], jax.Array, jax.Array]:
    """Sync-free concat for FIXED-WIDTH columns: parts stack at their full
    capacities (no compaction) and the returned (out_cap,) live MASK marks
    which rows are real — row counts stay device scalars, so no host
    round-trip. Downstream fused ops consume the mask via live_of
    (reference contrast: the cudf concat path syncs row counts;
    GpuCoalesceBatches.scala:398 — on TPU a sync costs a host round trip, so
    the merge loop avoids it entirely)."""
    caps = [cp[0].validity.shape[0] for cp in col_parts]
    masks = [
        jnp.arange(c, dtype=jnp.int32) < jnp.int32(cnt)
        for c, cnt in zip(caps, counts)
    ]
    mask = jnp.concatenate(masks)
    if mask.shape[0] < out_cap:
        mask = jnp.concatenate(
            [mask, jnp.zeros(out_cap - mask.shape[0], jnp.bool_)])
    else:
        mask = mask[:out_cap]
    ncols = len(col_parts[0])
    out: List[ColV] = []
    for j in range(ncols):
        parts = [cp[j] for cp in col_parts]
        data = jnp.concatenate([p.data for p in parts])
        valid = jnp.concatenate([p.validity for p in parts])
        if data.shape[0] < out_cap:
            pad = out_cap - data.shape[0]
            data = jnp.concatenate([data, jnp.zeros(pad, data.dtype)])
            valid = jnp.concatenate([valid, jnp.zeros(pad, jnp.bool_)])
        else:
            data, valid = data[:out_cap], valid[:out_cap]
        out.append(ColV(data, valid & mask))
    total = sum(jnp.int32(c) for c in counts)
    return out, mask, total


def concat_string(
    parts: Sequence[StrV],
    lengths: Sequence[int],
    byte_lengths: Sequence[int],
    out_cap: int,
    out_char_cap: int,
) -> StrV:
    offsets = jnp.zeros(out_cap + 1, jnp.int32)
    chars = jnp.zeros(out_char_cap, jnp.uint8)
    validity = jnp.zeros(out_cap, jnp.bool_)
    row_off = 0
    byte_off = 0
    for p, n, nb in zip(parts, lengths, byte_lengths):
        if n == 0:
            continue
        shifted = p.offsets[: n + 1] + jnp.int32(byte_off)
        offsets = lax.dynamic_update_slice(offsets, shifted, (row_off,))
        validity = lax.dynamic_update_slice(validity, p.validity[:n], (row_off,))
        if nb > 0:
            chars = lax.dynamic_update_slice(chars, p.chars[:nb], (byte_off,))
        row_off += n
        byte_off += nb
    total_rows, total_bytes = row_off, byte_off
    # keep offsets monotonic through the padded tail
    idx = jnp.arange(out_cap + 1, dtype=jnp.int32)
    offsets = jnp.where(idx <= total_rows, offsets, jnp.int32(total_bytes))
    return StrV(offsets, chars, validity)


def concat_pieces_traced(
    col_parts: Sequence[Sequence[Val]],
    counts: Sequence[jax.Array],
    byte_counts: Sequence[Sequence[jax.Array]],
    out_cap: int,
    out_char_caps: Sequence[int],
) -> Tuple[List[Val], jax.Array]:
    """Concat with TRACED row/byte counts — one XLA program per shape set.

    ``concat_batches_cols`` bakes host lengths into each dispatch, so every
    distinct length combination compiles a fresh executable; the exchange's
    reduce side sees arbitrary piece sizes every query and would compile
    forever. Here counts are operands: placement is masked
    ``dynamic_update_slice`` at traced starts into a sum-of-capacities work
    buffer (pieces applied in order, so each row's OWNING piece writes
    last), then a static head slice. Trace-safe under jit/shard_map.
    """
    k = len(col_parts)
    ncols = len(col_parts[0])
    counts_arr = jnp.stack([jnp.int32(c) for c in counts])
    row_offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts_arr)])
    total = row_offs[k]

    def place(parts: Sequence[jax.Array], lens) -> jax.Array:
        # work buffer >= out_cap so the final head slice never clamps, and
        # >= sum(caps) so no dynamic_update_slice start ever clamps
        caps = [int(p.shape[0]) for p in parts]
        work = jnp.zeros(max(sum(caps), out_cap), parts[0].dtype)
        for i, p in enumerate(parts):
            slot = jnp.arange(caps[i], dtype=jnp.int32)
            masked = jnp.where(slot < lens[i], p, jnp.zeros((), p.dtype))
            work = lax.dynamic_update_slice(work, masked, (row_offs[i],))
        return work

    out: List[Val] = []
    si = 0
    for j in range(ncols):
        parts = [cp[j] for cp in col_parts]
        if isinstance(parts[0], StrV):
            bc = [byte_counts[i][si] for i in range(k)]
            out_char_cap = out_char_caps[si]
            si += 1
            byte_offs = jnp.concatenate([
                jnp.zeros(1, jnp.int32),
                jnp.cumsum(jnp.stack([jnp.int32(b) for b in bc])),
            ])
            # per-row lengths placed like fixed data, then offsets by cumsum
            lens_parts = [p.offsets[1:] - p.offsets[:-1] for p in parts]
            lens_work = place(lens_parts, counts)[:out_cap]
            idx = jnp.arange(out_cap, dtype=jnp.int32)
            lens_work = jnp.where(idx < total, lens_work, 0)
            offsets = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(lens_work).astype(jnp.int32)])
            char_caps = [int(p.chars.shape[0]) for p in parts]
            cwork = jnp.zeros(max(sum(char_caps), out_char_cap), jnp.uint8)
            for i, p in enumerate(parts):
                slot = jnp.arange(char_caps[i], dtype=jnp.int32)
                masked = jnp.where(slot < bc[i], p.chars, jnp.uint8(0))
                cwork = lax.dynamic_update_slice(cwork, masked, (byte_offs[i],))
            chars = cwork[:out_char_cap]
            validity = place(
                [p.validity for p in parts], counts)[:out_cap]
            validity = validity & (idx < total)
            out.append(StrV(offsets, chars, validity))
        else:
            idx = jnp.arange(out_cap, dtype=jnp.int32)
            data = place([p.data for p in parts], counts)[:out_cap]
            validity = place(
                [p.validity for p in parts], counts)[:out_cap]
            validity = validity & (idx < total)
            data = jnp.where(validity, data, jnp.zeros((), data.dtype))
            out.append(ColV(data, validity))
    return out, total


def concat_batches_cols(
    col_parts: Sequence[Sequence[Val]],
    lengths: Sequence[int],
    byte_lengths_per_col: Sequence[Sequence[int]],
    out_cap: int,
    out_char_caps: Sequence[int],
) -> Tuple[List[Val], int]:
    """Concatenate N batches column-wise.

    ``col_parts[i]`` = columns of batch i; ``byte_lengths_per_col[i][j]`` =
    byte length of string column j in batch i (host ints, synced by the
    caller once per batch like cudf's row-count syncs).
    """
    ncols = len(col_parts[0])
    out: List[Val] = []
    si = 0
    for j in range(ncols):
        parts = [cp[j] for cp in col_parts]
        if isinstance(parts[0], StrV):
            bl = [byte_lengths_per_col[i][si] for i in range(len(col_parts))]
            out.append(
                concat_string(parts, lengths, bl, out_cap, out_char_caps[si])
            )
            si += 1
        else:
            out.append(concat_fixed(parts, lengths, out_cap))
    return out, sum(lengths)

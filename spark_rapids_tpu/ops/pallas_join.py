"""Pallas hash-join probe kernel — the PALLAS tier of
``spark.rapids.tpu.sql.join.strategy`` (the legacy
``sql.join.pallasProbe.enabled`` toggle still forces it under AUTO).

The general probe is a vectorized binary search over the sorted build
words — log2(build) gather passes, each at HBM-random-access speed, and
the r09 cost plane shows the probe programs touching XLA bytes at
`hbm_frac_xla` 0.0055. This kernel is the fast-memory alternative for
the broadcast-class case (small build side, one fixed-width key <= 2
u32 words): each grid step holds one (probe-block x build-tile) equality
mask in VMEM, reduces it to per-probe (first match, match count) there,
and accumulates across build tiles — the mask never exists in HBM and
no gather chain is emitted. Work is O(probe x build) compares, which
beats the search only while the build side is VMEM-tile small; the
strategy conf keeps it forced-only and :func:`ops.join.probe_ranges`
falls back to the search for multi-word keys.

Build rows [0, build_count) are the sorted JOINABLE rows (exec/join
sorts null-key and dead rows past the count), so equal keys are
contiguous and (first, count) is exactly the [lo, hi) contract of the
binary search. ``interpret=True`` runs the same kernel off-TPU (CPU CI).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..envinfo import pallas_interpret

#: what Mosaic (jax 0.9.0 / libtpu 0.0.34, asked for a v5e without the
#: chip, PR 23) said of the probe kernel at 2^21 x 2^21 rows
MOSAIC_REFUSAL = "RecursionError: maximum recursion depth exceeded"


def _interpret() -> bool:
    return pallas_interpret("ops/pallas_join", MOSAIC_REFUSAL)


#: probe rows / build rows per grid step (the VMEM equality-mask extent)
BLOCK_P = 256
BLOCK_BUILD = 256


def _probe_kernel(phi_ref, plo_ref, plive_ref, bhi_ref, blo_ref,
                  blive_ref, first_ref, cnt_ref, *, rb, sentinel):
    from jax.experimental import pallas as pl

    bj = pl.program_id(1)

    @pl.when(bj == 0)
    def _():
        first_ref[...] = jnp.full_like(first_ref, sentinel)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    eq = ((phi_ref[...][:, None] == bhi_ref[...][None, :])
          & (plo_ref[...][:, None] == blo_ref[...][None, :])
          & (blive_ref[...][None, :] != 0)
          & (plive_ref[...][:, None] != 0))  # (rp, rb) in VMEM only
    gidx = bj * rb + jax.lax.broadcasted_iota(jnp.int32, (1, rb), 1)
    cand = jnp.min(jnp.where(eq, gidx, sentinel), axis=1)
    first_ref[...] = jnp.minimum(first_ref[...], cand)
    cnt_ref[...] += jnp.sum(eq, axis=1, dtype=jnp.int32)


def pallas_probe_ranges(
    build_words: Sequence[jax.Array],
    build_count: jax.Array,
    probe_words: Sequence[jax.Array],
    probe_live: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """[lo, hi) of build matches per probe row — the Pallas lowering of
    :func:`ops.join.probe_ranges` for <= 2 u32 key words per side."""
    from jax.experimental import pallas as pl

    nb = build_words[0].shape[0]
    m = probe_words[0].shape[0]
    zero_b = jnp.zeros(nb, jnp.uint32)
    zero_p = jnp.zeros(m, jnp.uint32)
    bhi = build_words[0].astype(jnp.uint32)
    blo = (build_words[1].astype(jnp.uint32) if len(build_words) > 1
           else zero_b)
    phi = probe_words[0].astype(jnp.uint32)
    plo = (probe_words[1].astype(jnp.uint32) if len(probe_words) > 1
           else zero_p)
    blive = (jnp.arange(nb, dtype=jnp.int32)
             < build_count.astype(jnp.int32)).astype(jnp.int32)
    plive = probe_live.astype(jnp.int32)

    rp = min(BLOCK_P, max(8, m))
    rb = min(BLOCK_BUILD, max(8, nb))
    nbp = -(-m // rp)
    nbb = -(-nb // rb)
    sentinel = nbb * rb

    from .pallas_groupby import _pad_rows

    phi_p, plo_p, plive_p = _pad_rows([phi, plo, plive], m, rp, [0, 0, 0])
    bhi_p, blo_p, blive_p = _pad_rows([bhi, blo, blive], nb, rb, [0, 0, 0])

    first, cnt = pl.pallas_call(
        functools.partial(_probe_kernel, rb=rb, sentinel=sentinel),
        out_shape=(jax.ShapeDtypeStruct((nbp * rp,), jnp.int32),
                   jax.ShapeDtypeStruct((nbp * rp,), jnp.int32)),
        grid=(nbp, nbb),
        in_specs=[
            pl.BlockSpec((rp,), lambda pi, bi: (pi,)),
            pl.BlockSpec((rp,), lambda pi, bi: (pi,)),
            pl.BlockSpec((rp,), lambda pi, bi: (pi,)),
            pl.BlockSpec((rb,), lambda pi, bi: (bi,)),
            pl.BlockSpec((rb,), lambda pi, bi: (bi,)),
            pl.BlockSpec((rb,), lambda pi, bi: (bi,)),
        ],
        out_specs=(pl.BlockSpec((rp,), lambda pi, bi: (pi,)),
                   pl.BlockSpec((rp,), lambda pi, bi: (pi,))),
        interpret=_interpret(),
    )(phi_p, plo_p, plive_p, bhi_p, blo_p, blive_p)
    first, cnt = first[:m], cnt[:m]
    lo = jnp.where(cnt > 0, first, 0)
    lo = jnp.where(probe_live, lo, 0)
    cnt = jnp.where(probe_live, cnt, 0)
    return lo, lo + cnt

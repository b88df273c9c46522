"""Native (JAX/Pallas) UDF interface + in-tree example.

Reference analog: ``RapidsUDF.java:22`` — a UDF class implements
``evaluateColumnar(ColumnVector...) -> ColumnVector`` and the plugin runs
that instead of row-by-row JVM code; the in-tree example is a CUDA kernel
(udf-examples/src/main/cpp/src/string_word_count.cu, 93 LoC + JNI).

TPU equivalent: the user registers a COLUMNAR function written in
JAX/Pallas over the engine's device column values (ColV fixed-width,
StrV Arrow offsets+bytes), plus the ordinary row function for the CPU
fallback — mirroring how a RapidsUDF still has its row-based
``evaluate``. The columnar function is traced INTO the engine's fused
projection jit, so a native UDF fuses with the surrounding expressions
(better than the reference, which launches its kernel separately).

In-tree example: :func:`string_word_count` — the same UDF the reference
ships — with the per-byte kernel written in Pallas and the ragged
row-reduction in XLA.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

from .. import types as T
from ..envinfo import pallas_interpret
from ..expr import expressions as E


def tpu_udf(columnar_fn: Callable, row_fn: Callable,
            return_type: T.DataType):
    """Register a native TPU UDF (reference: RapidsUDF.evaluateColumnar).

    ``columnar_fn(cap, *vals) -> Val`` runs traced inside the engine's
    fused projection (vals are ColV/StrV); ``row_fn(*args)`` is the CPU
    fallback the oracle and untagged plans use. Returns a builder:
    ``wc = tpu_udf(...); expr = wc(col("s"))``.
    """

    def apply(*args: E.Expression) -> E.Expression:
        return E.NativeUDF(columnar_fn, row_fn, tuple(args), return_type)

    apply.columnar_fn = columnar_fn
    apply.row_fn = row_fn
    return apply


# ---------------------------------------------------------------------------
# in-tree example: string word count (reference: string_word_count.cu)
# ---------------------------------------------------------------------------
_BLOCK = 1024


def _word_start_kernel(chars_ref, prev_ref, out_ref):
    """Pallas kernel: out[i] = 1 iff byte i starts a word (non-space whose
    predecessor is a space). ``prev`` carries the byte before each block so
    blocks stay independent (the reference's CUDA kernel threads one byte
    per thread the same way)."""
    c = chars_ref[...]
    p = prev_ref[...]
    is_sp = _is_space(c)
    prev_sp = _is_space(p)
    out_ref[...] = ((~is_sp) & prev_sp).astype(out_ref.dtype)


def _is_space(b):
    import jax.numpy as jnp

    # the reference's kernel treats ASCII whitespace as delimiters
    return (
        (b == 0x20) | (b == 0x09) | (b == 0x0A)
        | (b == 0x0B) | (b == 0x0C) | (b == 0x0D)
    )


#: what Mosaic (jax 0.9.0 / libtpu 0.0.34, asked for a v5e without the
#: chip, PR 23) said of the word-start kernel over 2^24 chars
MOSAIC_REFUSAL = (
    "Not implemented: changeBitwidth when minor tiling is not 128")


def _interpret() -> bool:
    return pallas_interpret("udf/native word_starts", MOSAIC_REFUSAL)


def _word_starts_pallas(chars):
    """(nchars,) int32 word-start flags via the Pallas kernel (interpret
    mode off-TPU so the same kernel runs under the CPU test mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = chars.shape[0]
    pad = (-n) % _BLOCK
    c = jnp.concatenate([chars, jnp.full(pad, 0x20, jnp.uint8)]) if pad else chars
    total = c.shape[0]
    # byte BEFORE each position (space before position 0: row handling is
    # done by the ragged reduction, which re-bases at row starts)
    prev = jnp.concatenate([jnp.full(1, 0x20, jnp.uint8), c[:-1]])
    interpret = _interpret()
    flags = pl.pallas_call(
        _word_start_kernel,
        out_shape=jax.ShapeDtypeStruct((total,), jnp.int32),
        grid=(total // _BLOCK,),
        in_specs=[
            pl.BlockSpec((_BLOCK,), lambda i: (i,)),
            pl.BlockSpec((_BLOCK,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((_BLOCK,), lambda i: (i,)),
        interpret=interpret,
    )(c, prev)
    return flags[:n]


def _word_count_columnar(cap: int, s):
    """Columnar word count over a StrV: Pallas per-byte kernel + XLA ragged
    reduction (prefix-sum difference at row offsets — no scatter)."""
    import jax.numpy as jnp

    from ..expr.eval import ColV, StrV

    assert isinstance(s, StrV), "string_word_count takes a string column"
    flags = _word_starts_pallas(s.chars)
    nch = s.chars.shape[0]
    P = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(flags).astype(jnp.int32)])
    lo = jnp.clip(s.offsets[:-1], 0, nch)
    hi = jnp.clip(s.offsets[1:], 0, nch)
    counts = P[hi] - P[lo]
    # within-row boundary: a row starting mid-pool with a non-space first
    # byte whose global predecessor was non-space still starts a word
    first = jnp.take(s.chars, jnp.clip(lo, 0, max(nch - 1, 0)), mode="clip")
    prev = jnp.take(
        s.chars, jnp.clip(lo - 1, 0, max(nch - 1, 0)), mode="clip")
    fix = (
        (hi > lo)
        & ~_is_space(first)
        & jnp.where(lo > 0, ~_is_space(prev), False)
    )
    counts = counts + fix.astype(jnp.int32)
    return ColV(counts.astype(jnp.int32), s.validity)


def _word_count_row(s: Optional[str]) -> Optional[int]:
    if s is None:
        return None
    # ASCII whitespace only, matching the device kernel (and the
    # reference's CUDA kernel) — python str.split() would also split on
    # unicode spaces
    import re

    return sum(1 for w in re.split("[ \t\n\x0b\x0c\r]+", s) if w)


#: the in-tree native UDF (reference: StringWordCount.java + the CUDA
#: kernel): ``string_word_count(col("s"))`` in any projection
string_word_count = tpu_udf(_word_count_columnar, _word_count_row, T.INT)

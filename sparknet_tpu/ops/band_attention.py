"""A windowed causal attention core as two Pallas kernels, forward and
backward, that walk the window's band.

Under a window of w, query t sees keys t - w + 1 .. t: of the S / b key
blocks of width b, query block i reaches blocks i - r .. i, r =
(w - 2) // b + 1 (one block back at w <= b + 1).  jax's splash kernels
give that band two backward forms, both costly there
(``ops/attention.py``, PERF.md section 6, PR 51): the fused kernel's grid
is every (key block, head, query block) whatever the mask, and its dq is
one q-sized partial a KEY block, summed by XLA; the separate dq and dkv
kernels walk the band, but compute the scores and their exponentials
twice (seven products for five); both visit whole b x b blocks, of which
the band fills half at w = b, and their forward keeps a query's
logsumexp 128 lanes wide.

Both kernels here take a grid step a (batch, key head, query block i,
query head g of the group that shares the key head), the last two
sequential, and hold key blocks i - r .. i of K and V in VMEM, where they
stay over the group's heads.  A query block is cut into sub-blocks of
``SUB`` rows; sub-block s takes only the keys its rows can see, rounded
out to whole lane groups: (w + SUB) keys for SUB rows (1.5 x the mask's
pairs at w = 512, SUB = 256, where a 512 x 512 block visits 2 x).  Scores
are computed key-major ([keys, queries], as jax's dkv kernel does), so a
query's statistics lie along lanes, dV += P^T dO and dK += dS^T Q are
plain products, and the band's mask is ONE additive table for every
sub-block of every step (the band looks the same from each).

* forward: two products; o and the scores' logsumexp (f32, one lane a
  query) written once.
* backward: the fused backward's five products.  dq is summed in f32 over
  a sub-block's keys and written ONCE; dK and dV are summed in an f32
  ring of r + 1 key blocks in VMEM over the group's heads and the
  consecutive query blocks that reach a key block; after query block i
  the ring's oldest block (i - r) is complete, is written, and the ring
  moves up one.  r more steps at the end flush it.

Nothing of S / b times q's size exists.  Timed alone and in the cells:
``tools/attn_core_kernel.py``, :func:`ops.attention.attention_core`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a query sub-block, lanes of the key-major scores: 256 timed the
# fastest of 128 / 256 / 512 at both windowed shapes the cells run (128
# visits fewer pairs, 1.25 x the mask's, and takes more, smaller products)
SUB = 256
_NT = (((1,), (1,)), ((), ()))  # x yᵀ
# the band's bias whole, at every grid step the same block: fetched once
_BIAS_SPEC = pl.BlockSpec(None, lambda b, h, i, g: (0, 0))


def reach(window: int, block: int) -> int:
    """How many key blocks BEHIND its own a query block's window reaches."""
    return (window - 2) // block + 1


def _span(sub: int, window: int) -> int:
    """Key rows a sub-block of ``sub`` query rows takes: from the first
    key its first row can see, rounded down to a lane group, to its own
    last row."""
    return sub + -(-(window - 1) // 128) * 128


def _pieces(s: int, sub: int, block: int, r: int, window: int):
    """(slot, from, to, at) of the key rows sub-block ``s`` (of ``sub``
    rows) of a query block takes from each of the r + 1 key blocks held
    (slot r is the query block's own), ``at`` their first row within the
    sub-block's span (:func:`_span`)."""
    hi = r * block + (s + 1) * sub  # past its last row, from slot 0's
    lo = hi - _span(sub, window)    # >= 0: r blocks hold window - 1 keys
    out = []
    for c in range(r + 1):
        a, b = max(lo, c * block), min(hi, (c + 1) * block)
        if a < b:
            out.append((c, a - c * block, b - c * block, a - lo))
    return out


def _band_bias(sub: int, window: int):
    """f32 [span, sub], key-major: 0 where query row t of a sub-block
    sees key row j of its span, -1e30 where the window hides it.  One
    table for every sub-block of every query block: the band looks the
    same from each."""
    span = _span(sub, window)
    ahead = (span - sub + np.arange(sub))[None, :] - np.arange(span)[:, None]
    return np.where((ahead >= 0) & (ahead < window), 0.0, -1e30).astype(
        np.float32)


def _masked(st, bias_ref, at, i, r, c):
    """Key-major scores ``st`` of the key rows ``at`` onward of a span,
    held in slot ``c``, under the band's bias; before key block r a slot
    behind the query block's own holds no block (its index was clamped):
    all of it hidden."""
    st = st + bias_ref[at:at + st.shape[0], :]
    return st if c == r else jnp.where(i - r + c >= 0, st, -1e30)


def _geometry(S: int, block: int, window: int, sub: int):
    """(query blocks, key blocks behind its own a query block reaches,
    rows of a sub-block: a block's own where it is narrower)."""
    sub = min(sub, block)
    if S % block or block % sub or sub % 128 or not 0 < window < S:
        raise ValueError(f"S {S} in blocks of {block} in sub-blocks of "
                         f"{sub} under a window of {window}")
    return S // block, reach(window, block), sub


def _key_specs(block: int, r: int, n: int, *widths: int):
    """The r + 1 key blocks a step holds, of K and then of V: slot c is
    key block i - r + c, clamped into the sequence (:func:`_masked` hides
    a slot before the first block; the backward's flush steps read none)."""
    slot = lambda c: lambda b, h, i, g: (
        b, h, jnp.clip(i - r + c, 0, n - 1), 0)
    return [pl.BlockSpec((None, None, block, width), slot(c))
            for width in widths for c in range(r + 1)]


def _bwd_kernel(q_ref, do_ref, lse_ref, di_ref, bias_ref, *refs, block: int,
                sub: int, r: int, window: int, n: int, group: int):
    k_refs, v_refs = refs[:r + 1], refs[r + 1:2 * r + 2]
    dq_ref, dk_ref, dv_ref, dk_ring, dv_ring = refs[2 * r + 2:]
    i, g = pl.program_id(2), pl.program_id(3)
    f32 = jnp.float32

    @pl.when((i == 0) & (g == 0))
    def _():
        dk_ring[...] = jnp.zeros_like(dk_ring)
        dv_ring[...] = jnp.zeros_like(dv_ring)

    @pl.when(i < n)
    def _():
        for s in range(block // sub):
            rows = slice(s * sub, (s + 1) * sub)
            q, do = q_ref[rows, :], do_ref[rows, :]
            lse, di = lse_ref[:, rows], di_ref[:, rows]  # [1, sub]
            dq = jnp.zeros((sub, q.shape[1]), f32)
            for c, a, b, at in _pieces(s, sub, block, r, window):
                k, v = k_refs[c][a:b, :], v_refs[c][a:b, :]
                ring = slice(c * block + a, c * block + b)
                # key-major: [keys, queries]
                st = lax.dot_general(k, q, _NT, preferred_element_type=f32)
                pt = jnp.exp(_masked(st, bias_ref, at, i, r, c) - lse)
                dv_ring[ring, :] += jnp.dot(pt.astype(do.dtype), do,
                                            preferred_element_type=f32)
                dpt = lax.dot_general(v, do, _NT, preferred_element_type=f32)
                dst = (dpt - di) * pt
                dk_ring[ring, :] += jnp.dot(dst.astype(q.dtype), q,
                                            preferred_element_type=f32)
                dq += jnp.dot(dst.T.astype(k.dtype), k,
                              preferred_element_type=f32)
            dq_ref[rows, :] = dq.astype(dq_ref.dtype)

    @pl.when(g == group - 1)
    def _():
        # after query block i the oldest key block held, i - r, has every
        # query block that reaches it (before block r it is no block: the
        # mask left it zeros, and its write lands on block 0 ahead of the
        # real one)
        dk_ref[...] = dk_ring[:block, :].astype(dk_ref.dtype)
        dv_ref[...] = dv_ring[:block, :].astype(dv_ref.dtype)
        if r:
            dk_ring[:r * block, :] = dk_ring[block:, :]
            dv_ring[:r * block, :] = dv_ring[block:, :]
        dk_ring[r * block:, :] = jnp.zeros((block, dk_ring.shape[1]), f32)
        dv_ring[r * block:, :] = jnp.zeros((block, dv_ring.shape[1]), f32)


def band_bwd(q, k, v, o, lse, do, block: int, window: int,
             interpret: bool = False, sub: int = SUB):
    """(dq, dk, dv) of the windowed causal core for q [B, Hk, G, S, D]
    (carrying the scores' scale), k [B, Hk, S, D], v [B, Hk, S, Dv], the
    forward's o [B, Hk, G, S, Dv] and logsumexp [B, Hk, G, S] and the
    cotangent do, at ``block``-wide query and key blocks in sub-blocks of
    ``sub`` rows (a block's own where it is narrower)."""
    B, Hk, G, S, D = q.shape
    Dv = v.shape[-1]
    n, r, sub = _geometry(S, block, window, sub)
    di = jnp.einsum("bhgsd,bhgsd->bhgs", o.astype(jnp.float32),
                    do.astype(jnp.float32))
    rows = lambda x: x[:, :, :, None, :]  # a query a lane: [.., 1, S]
    last = n - 1
    # at the r flush steps the query-side blocks stay where they were
    head = lambda i, g: jnp.where(i < n, g, G - 1)
    of_q = lambda b, h, i, g: (b, h, head(i, g), jnp.minimum(i, last), 0)
    of_row = lambda b, h, i, g: (b, h, head(i, g), 0, jnp.minimum(i, last))
    q_spec = lambda width: pl.BlockSpec((None, None, None, block, width), of_q)
    row_spec = pl.BlockSpec((None, None, None, 1, block), of_row)
    key_specs = _key_specs(block, r, n, D, Dv)
    kernel = functools.partial(_bwd_kernel, block=block, sub=sub, r=r,
                               window=window, n=n, group=G)
    return pl.pallas_call(
        kernel,
        grid=(B, Hk, n + r, G),
        in_specs=[q_spec(D), q_spec(Dv), row_spec, row_spec, _BIAS_SPEC]
        + key_specs,
        # dK and dV of slot 0: key block i - r, complete after step i
        out_specs=[q_spec(D), key_specs[0], key_specs[r + 1]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM(((r + 1) * block, D), jnp.float32),
                        pltpu.VMEM(((r + 1) * block, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name="band_attention_bwd", interpret=interpret,
    )(q, do, rows(lse), rows(di), _band_bias(sub, window), *[k] * (r + 1),
      *[v] * (r + 1))


def _fwd_kernel(q_ref, bias_ref, *refs, block: int, sub: int, r: int,
                window: int):
    k_refs, v_refs = refs[:r + 1], refs[r + 1:2 * r + 2]
    o_ref, lse_ref = refs[2 * r + 2:]
    i = pl.program_id(2)
    f32 = jnp.float32
    for s in range(block // sub):
        rows = slice(s * sub, (s + 1) * sub)
        q = q_ref[rows, :]
        pieces = _pieces(s, sub, block, r, window)
        # key-major [keys, queries]: a query's statistics lie along lanes,
        # as the backward reads them
        sts = [_masked(lax.dot_general(k_refs[c][a:b, :], q, _NT,
                                       preferred_element_type=f32),
                       bias_ref, at, i, r, c) for c, a, b, at in pieces]
        m = functools.reduce(jnp.maximum, (
            jnp.max(st, axis=0, keepdims=True) for st in sts))  # [1, sub]
        pts = [jnp.exp(st - m) for st in sts]
        total = sum(jnp.sum(pt, axis=0, keepdims=True) for pt in pts)
        inv = 1.0 / total
        o = jnp.zeros((sub, o_ref.shape[1]), f32)
        for (c, a, b, _), pt in zip(pieces, pts):
            v = v_refs[c][a:b, :]
            o += jnp.dot((pt * inv).T.astype(v.dtype), v,
                         preferred_element_type=f32)
        o_ref[rows, :] = o.astype(o_ref.dtype)
        lse_ref[:, rows] = m + jnp.log(total)


def band_fwd(q, k, v, block: int, window: int, interpret: bool = False,
             sub: int = SUB):
    """(o, logsumexp) of the windowed causal core, shapes as
    :func:`band_bwd`'s: the same walk of the band, two products."""
    B, Hk, G, S, D = q.shape
    Dv = v.shape[-1]
    n, r, sub = _geometry(S, block, window, sub)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, sub=sub, r=r,
                          window=window),
        grid=(B, Hk, n, G),
        in_specs=[pl.BlockSpec((None, None, None, block, D),
                               lambda b, h, i, g: (b, h, g, i, 0)),
                  _BIAS_SPEC] + _key_specs(block, r, n, D, Dv),
        out_specs=[pl.BlockSpec((None, None, None, block, Dv),
                                lambda b, h, i, g: (b, h, g, i, 0)),
                   pl.BlockSpec((None, None, None, 1, block),
                                lambda b, h, i, g: (b, h, g, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, Hk, G, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hk, G, 1, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name="band_attention_fwd", interpret=interpret,
    )(q, _band_bias(sub, window), *[k] * (r + 1), *[v] * (r + 1))
    return o, lse[:, :, :, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def band_core(q, k, v, block: int, window: int, interpret: bool = False,
              sub: int = SUB):
    """The windowed causal core for q [B, Hk, G, S, D] carrying the
    scores' scale, k [B, Hk, S, D] and v [B, Hk, S, Dv] -> o
    [B, Hk, G, S, Dv]."""
    return band_fwd(q, k, v, block, window, interpret, sub)[0]


def _core_fwd(q, k, v, block, window, interpret, sub):
    o, lse = band_fwd(q, k, v, block, window, interpret, sub)
    return o, (q, k, v, o, lse)


def _core_bwd(block, window, interpret, sub, res, do):
    return band_bwd(*res, do, block, window, interpret, sub)


band_core.defvjp(_core_fwd, _core_bwd)

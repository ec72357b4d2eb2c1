"""The gated delta-rule scan of a Gated-DeltaNet mixer
(``models/qwen3_next.py``): the chunked WY form in XLA, and as one Pallas
kernel for the TPU in which neither the masks, the solve nor the per-chunk
states leave VMEM.

The recurrence, a value head at a time, on a state ``S`` (``key_dim x
value_dim``, ``S_0`` = ``initial_state`` or zero), with ``alpha_t =
exp(g_t)`` (``g_t <= 0``) and ``beta_t`` in (0, 1)::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

Value head ``j`` reads key head ``j // (value heads / key heads)`` of ``q``
and ``k``. **The update READS the state** (``S^T k``): unlike Mamba-2's
(``ops/ssd_scan.py``), a chunk cannot be closed from its inputs alone. With
``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` the state is ``S_t = alpha_t
S_{t-1} + k_t u_t^T``, a gated linear attention over the pseudo-values
``u``; and inside a chunk of ``C`` positions, with ``gamma`` the running sum
of ``g`` and ``S`` the state the chunk was handed, the ``u`` solve a
unit-lower-triangular system (rows are positions)::

    A = -strict_lower(diag(beta) (K K^T) * exp(gamma_i - gamma_j))
    T = (I - A)^-1 = (I + A)(I + A^2)(I + A^4) ...      # A^C = 0
    U = T (beta V)         W = T (beta K * exp(gamma))
    V' = U - W S                                        # the chunk's u
    O  = (Q * exp(gamma)) S + tril((Q K^T) * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Everything ahead of ``V'`` is the chunk's own and runs for all chunks at
once; the three products against ``S`` are the one sequential chain, ``T /
C`` links long. ``_delta_xla`` is that in ``jax.numpy``: the numerics
oracle of the kernel, and what a CPU host, a mesh or a declined shape runs
(never a scan over single positions). ``_delta_pallas`` is a grid over
(row, group of key heads, chunk), chunks innermost and in order, the
group's value heads' states in a VMEM scratch across a row's chunks. It
takes the one shape in which a key head's value heads fill a lane tile
exactly — chunks of 64 under two value heads a key head — and STACKS the
two heads along the rows of one block-diagonal ``A``: a step forms ``K
K^T`` and ``Q K^T`` once a key head, then one mask, one ``T``, one ``U``,
one ``W`` and one masked product of full lane tiles serve both heads, and
only the products against the heads' own states are a head's.
``tools/delta_alone.py`` times it beside the XLA form on the chip
(``tools/delta_alone_pr54.json``).

Precision, both forms: float32 running sums, decays, masks, ``A`` and the
products that make ``T`` (float32 operands at three bfloat16 passes of the
MXU each, ``Precision.HIGH``, written out in the kernel where Mosaic has no
such mode: ``T`` is an inverse),
``U``, ``V'``, the state and every accumulation; the matmul operands
elsewhere (``q``, ``k``, ``v``, ``T`` where ``U`` and ``W`` read it, ``W``,
``V'`` and the state where they are operands, the masked scores) in ``v``'s
dtype — bfloat16 where the configuration's weights are, float32 in the
float32 tests.

``delta_refusal`` is the ONE predicate on shapes: the traced guard in
``models/qwen3_next.py``, the scorer's engagement counters and the tests ask
it, and it answers by name. Nothing chooses between the forms but it and
``use_pallas`` (the flag the other kernels get: a one-device TPU program).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# the kernel's one chunk, and the value heads a key head that fill a lane
# tile with it
KERNEL_CHUNK = 64
KERNEL_RATIO = LANES // KERNEL_CHUNK


def delta_refusal(seq_len: int, key_dim: int, value_dim: int, chunk: int,
                  num_key_heads: int, num_value_heads: int) -> Optional[str]:
    """Why the Pallas form does not take a shape, by name, or None where it
    does (the XLA form takes any)."""
    if key_dim != LANES or value_dim != LANES:
        return (f"gated_delta_scan's kernel takes a state of one lane tile "
                f"of {LANES} a side: key_dim {key_dim}, value_dim "
                f"{value_dim}")
    if chunk != KERNEL_CHUNK:
        return (f"gated_delta_scan's kernel takes chunks of {KERNEL_CHUNK}: "
                f"chunk {chunk}")
    if seq_len < chunk or seq_len % chunk:
        return (f"gated_delta_scan's kernel takes whole chunks of {chunk} "
                f"positions: seq_len {seq_len}")
    if num_value_heads != KERNEL_RATIO * num_key_heads:
        return (f"gated_delta_scan's kernel stacks {KERNEL_RATIO} value "
                f"heads a key head into one lane tile: {num_value_heads} "
                f"value heads over {num_key_heads} key heads")
    return None


def _solve_steps(chunk: int) -> int:
    """Squarings of ``A`` that reach ``A^chunk = 0``."""
    return max((chunk - 1).bit_length() - 1, 0)


def _inverse(a: jax.Array, chunk: int) -> jax.Array:
    """``(I - a)^-1`` of a strictly lower-triangular ``a`` ``f32[..., C,
    C]``: the product ``(I + a)(I + a^2)(I + a^4)...`` — the factors
    commute, so ``T <- T + T P`` with ``P`` squared a step."""
    precision = jax.lax.Precision.HIGH
    t = a + jnp.eye(chunk, dtype=a.dtype)
    p = a
    for _ in range(_solve_steps(chunk)):
        p = jnp.matmul(p, p, precision=precision)
        t = t + jnp.matmul(t, p, precision=precision)
    return t


def _halves(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A float32 array as two bfloat16: its rounding, and what that left."""
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _solve_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """A float32 product of the solve inside the kernel at three bfloat16
    passes of the MXU: each operand as a high and a low half, the low x low
    term left out (``Precision.HIGH``, which a Pallas dot cannot ask for)."""
    f32 = jnp.float32
    a_high, a_low = _halves(a)
    b_high, b_low = (a_high, a_low) if b is a else _halves(b)
    return jnp.dot(a_high, b_high, preferred_element_type=f32) \
        + jnp.dot(a_high, b_low, preferred_element_type=f32) \
        + jnp.dot(a_low, b_high, preferred_element_type=f32)


def _delta_xla(q, k, v, g, beta, chunk: int, initial_state
               ) -> Tuple[jax.Array, jax.Array]:
    """The chunked algorithm in ``jax.numpy`` (module docstring). Every
    contraction is written with its batch indices first: the CPU backend
    has no bfloat16 contraction with a batch dimension that is not
    leading."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, nc, f32, operand = hv // hk, t // chunk, jnp.float32, v.dtype
    # [B, chunks, key heads, (value heads a key head,) C(, D)]
    qc = q.reshape(b, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
    kc = k.reshape(b, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
    vc = v.reshape(b, nc, chunk, hk, r, dv).transpose(0, 1, 3, 4, 2, 5)
    gam = jnp.cumsum(g.astype(f32).reshape(b, nc, chunk, hk, r).transpose(
        0, 1, 3, 4, 2), axis=-1)
    bet = beta.astype(f32).reshape(b, nc, chunk, hk, r).transpose(
        0, 1, 3, 4, 2)

    kk = jnp.einsum("bnhcd,bnhsd->bnhcs", kc, kc,
                    preferred_element_type=f32)[:, :, :, None]
    qk = jnp.einsum("bnhcd,bnhsd->bnhcs", qc, kc,
                    preferred_element_type=f32)[:, :, :, None]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    before = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    apart = gam[..., :, None] - gam[..., None, :]       # gamma_i - gamma_j
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, apart, 0.0)), 0.0)
    solve = _inverse(
        jnp.where(before, -(bet[..., :, None] * kk * decay), 0.0), chunk)
    grown = jnp.exp(gam)[..., None]                     # exp(gamma_i)
    k32, q32 = (x[:, :, :, None].astype(f32) for x in (kc, qc))
    u = jnp.einsum(
        "bnhrcs,bnhrsd->bnhrcd", solve.astype(operand),
        (bet[..., None] * vc.astype(f32)).astype(operand),
        preferred_element_type=f32)
    w = jnp.einsum(
        "bnhrcs,bnhrsd->bnhrcd", solve.astype(operand),
        (bet[..., None] * grown * k32).astype(operand),
        preferred_element_type=f32).astype(operand)
    masked = (qk * decay).astype(operand)
    q_in = (q32 * grown).astype(operand)
    k_out = (k32 * jnp.exp(gam[..., -1:] - gam)[..., None]).astype(operand)
    whole = jnp.exp(gam[..., -1])                       # [B, nc, Hk, r]

    s0 = (jnp.zeros((b, hk, r, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32).reshape(b, hk, r, dk, dv))

    def link(state, chunk_of):
        u_c, w_c, masked_c, q_c, k_c, whole_c = chunk_of
        held = state.astype(operand)
        new = u_c - jnp.einsum("bhrck,bhrkd->bhrcd", w_c, held,
                               preferred_element_type=f32)
        new_op = new.astype(operand)
        out = jnp.einsum("bhrck,bhrkd->bhrcd", q_c, held,
                         preferred_element_type=f32) \
            + jnp.einsum("bhrcs,bhrsd->bhrcd", masked_c, new_op,
                         preferred_element_type=f32)
        state = whole_c[..., None, None] * state + jnp.einsum(
            "bhrck,bhrcd->bhrkd", k_c, new_op, preferred_element_type=f32)
        return state, out

    final, out = jax.lax.scan(
        link, s0, tuple(jnp.moveaxis(x, 1, 0)
                        for x in (u, w, masked, q_in, k_out, whole)))
    # [chunks, B, Hk, r, C, Dv] -> [B, T, Hv, Dv]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, hv, dv)
    return out, final.reshape(b, hv, dk, dv)


def _delta_kernel(*refs, key_heads: int, ratio: int, chunk: int,
                  carried: bool):
    """One (row, group of key heads, chunk) step: ``key_heads`` key heads
    and their ``ratio`` value heads each over one chunk of one row, the
    value heads' states in ``state`` (scratch) since the row's first chunk.
    A key head's value heads are STACKED along the rows of one ``[128,
    128]`` tile (``chunk x ratio`` = 128): ``A`` is block-diagonal, a block
    a head, so ONE solve, one ``U``, one ``W`` and one masked product serve
    the heads together at full lane tiles; only the products against the
    heads' own states are a head's. ``gam_ref`` and ``beta_ref`` arrive a
    key head a row, its heads side by side: ``[1, 1, key heads, ratio x
    chunk]``."""
    refs = list(refs)
    q_ref, k_ref, v_ref, gam_ref, beta_ref = (refs.pop(0) for _ in range(5))
    s0_ref = refs.pop(0) if carried else None
    o_ref, final_ref, state = refs
    chunk_i, chunks = pl.program_id(2), pl.num_programs(2)
    f32, operand, d = jnp.float32, v_ref.dtype, LANES

    @pl.when(chunk_i == 0)
    def _first_chunk():
        state[...] = (s0_ref[0] if carried else jnp.zeros_like(state))

    rows_ = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    same = cols == rows_
    # a row's head is ``row // chunk``; a pair of positions is seen inside
    # one head alone
    together = (rows_ // chunk) == (cols // chunk)
    seen, before = together & (cols <= rows_), together & (cols < rows_)
    # the lane of a row's OWN head's last position
    own_last = cols == (rows_ // chunk) * chunk + chunk - 1

    def down(row):
        # a key head's [1, 128] row as a [128, 1] column: the diagonal of
        # its broadcast, summed along the lanes
        return jnp.sum(jnp.where(same, row, 0.0), axis=1, keepdims=True)

    def against(x, y):                  # x y^T over the last axis of both
        return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def key_head(i, carry):
        at = pl.ds(pl.multiple_of(i * d, d), d)
        q = jnp.concatenate([q_ref[0, :, at]] * ratio, axis=0)  # [128, Dk]
        k = jnp.concatenate([k_ref[0, :, at]] * ratio, axis=0)
        kk, qk = against(k, k), against(q, k)                   # [128, 128]
        q32, k32 = q.astype(f32), k.astype(f32)
        gam_s = gam_ref[0, 0, pl.ds(i, 1), :]                   # [1, 128]
        beta_t, gam_t = down(beta_ref[0, 0, pl.ds(i, 1), :]), down(gam_s)
        decay = jnp.where(
            seen, jnp.exp(jnp.where(seen, gam_t - gam_s, 0.0)), 0.0)
        a = jnp.where(before, -(beta_t * kk * decay), 0.0)
        solve, p = a + same.astype(f32), a
        for _ in range(_solve_steps(chunk)):
            p = _solve_dot(p, p)
            solve = solve + _solve_dot(solve, p)
        solve = solve.astype(operand)
        grown = jnp.exp(gam_t)                                  # [128, 1]
        heads = [i * ratio + j for j in range(ratio)]
        v = jnp.concatenate([
            v_ref[0, :, pl.ds(pl.multiple_of(h * d, d), d)] for h in heads],
            axis=0).astype(f32)                                 # [128, Dv]
        u = jnp.dot(solve, (beta_t * v).astype(operand),
                    preferred_element_type=f32)
        w = jnp.dot(solve, (beta_t * grown * k32).astype(operand),
                    preferred_element_type=f32).astype(operand)
        q_in = (q32 * grown).astype(operand)
        states = [state[h] for h in heads]
        held = [s_in.astype(operand) for s_in in states]

        def by_head(x):                 # a head's rows against ITS state
            return jnp.concatenate([
                jnp.dot(x[j * chunk:(j + 1) * chunk], held[j],
                        preferred_element_type=f32)
                for j in range(ratio)], axis=0)

        new = u - by_head(w)
        new_op = new.astype(operand)
        out = by_head(q_in) + jnp.dot((qk * decay).astype(operand), new_op,
                                      preferred_element_type=f32)
        # gamma at the end of a row's own head's chunk
        end_t = jnp.sum(jnp.where(own_last, gam_s, 0.0), axis=1,
                        keepdims=True)                          # [128, 1]
        k_out = (k32 * jnp.exp(end_t - gam_t)).astype(operand)
        for j, h in enumerate(heads):
            mine = slice(j * chunk, (j + 1) * chunk)
            o_ref[0, :, pl.ds(pl.multiple_of(h * d, d), d)] = out[mine].astype(
                o_ref.dtype)
            # that head's end of chunk, down a column as tall as the state
            # (a [1, 1] value broadcasts along one axis at a time)
            end = jnp.sum(jnp.where(cols == j * chunk + chunk - 1, gam_s,
                                    0.0), axis=1, keepdims=True)
            state[h] = jnp.exp(end) * states[j] + jax.lax.dot_general(
                k_out[mine], new_op[mine], (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
        return carry

    # unrolled where it is lowered, not in Python: the body is traced once
    # a key head (``ops/attention._whole_row_kernel`` says what a Python
    # loop cost)
    jax.lax.fori_loop(0, key_heads, key_head, 0, unroll=True)

    @pl.when(chunk_i == chunks - 1)
    def _last_chunk():
        final_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _delta_pallas(q, k, v, g, beta, initial_state, *, chunk: int,
                  interpret: bool) -> Tuple[jax.Array, jax.Array]:
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    refusal = delta_refusal(t, dk, dv, chunk, hk, hv)
    if refusal:
        raise ValueError(refusal)
    ratio, nc, f32 = hv // hk, t // chunk, jnp.float32
    # key heads a grid step: the fewest that fill a sublane tile of the
    # per-head rows of ``gamma`` and ``beta``, else all of them
    key_heads = next(n for n in range(1, hk + 1) if hk % n == 0
                     and (n % SUBLANES == 0 or n == hk))
    held = key_heads * ratio            # value heads a step

    def by_head(x):                     # [B, T, Hv] -> [B, chunks, Hv, C]
        return x.astype(f32).reshape(b, nc, chunk, hv).transpose(0, 1, 3, 2)

    # a key head a row, its value heads side by side along the lanes
    steps = [x.reshape(b, nc, hk, ratio * chunk)
             for x in (jnp.cumsum(by_head(g), axis=-1), by_head(beta))]
    per_head = pl.BlockSpec((1, 1, key_heads, ratio * chunk),
                            lambda i, n, c: (i, c, n, 0))
    operands = [q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
                v.reshape(b, t, hv * dv), *steps]
    keys = pl.BlockSpec((1, chunk, key_heads * dk), lambda i, n, c: (i, c, n))
    values = pl.BlockSpec((1, chunk, held * dv), lambda i, n, c: (i, c, n))
    states = pl.BlockSpec((1, held, dk, dv), lambda i, n, c: (i, n, 0, 0))
    in_specs = [keys, keys, values, per_head, per_head]
    if initial_state is not None:
        in_specs.append(states)
        operands.append(initial_state.astype(f32))
    out, final = pl.pallas_call(
        functools.partial(_delta_kernel, key_heads=key_heads, ratio=ratio,
                          chunk=chunk, carried=initial_state is not None),
        name="gated_delta_scan",
        grid=(b, hk // key_heads, nc),
        in_specs=in_specs,
        out_specs=(values, states),
        out_shape=(jax.ShapeDtypeStruct((b, t, hv * dv), f32),
                   jax.ShapeDtypeStruct((b, hv, dk, dv), f32)),
        scratch_shapes=[pltpu.VMEM((held, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, t, hv, dv), final


def gated_delta_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, chunk: int,
                     initial_state: Optional[jax.Array] = None,
                     use_pallas: bool = False, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """``(o f32[B, T, Hv, Dv], final_state f32[B, Hv, Dk, Dv])`` of the
    recurrence in the module docstring: ``q`` and ``k`` ``[B, T, Hk, Dk]``
    (as the mixer hands them: normalised, ``q`` scaled) and ``v`` ``[B, T,
    Hv, Dv]`` in one dtype, ``g`` ``f32[B, T, Hv]`` (the log of the decay,
    not positive) and ``beta`` ``f32[B, T, Hv]``; ``initial_state`` ``f32[B,
    Hv, Dk, Dv]`` or None (zero) — ``ops.ssd_scan``'s signature, so that a
    cache of states can take either. A sequence cut in two, the first part's
    ``final_state`` handed on as the second's ``initial_state``, gives what
    the whole gives. ``use_pallas`` asks for the kernel; a shape
    ``delta_refusal`` names runs the XLA form, as does a ``T`` that is no
    whole number of chunks (padded with steps of ``g`` 0 and ``beta`` 0,
    which leave the state alone). ``interpret=True`` runs the kernel
    through the Pallas interpreter."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if use_pallas and delta_refusal(t, dk, dv, chunk, hk, hv) is None:
        return _delta_pallas(q, k, v, g, beta, initial_state, chunk=chunk,
                             interpret=interpret)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    out, final = _delta_xla(q, k, v, g, beta, chunk, initial_state)
    return out[:, :t], final

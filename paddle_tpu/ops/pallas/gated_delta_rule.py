"""The chunked gated delta rule as two TPU Pallas (Mosaic) kernels, one a pass.

``nn/functional/linear_attention.py`` defines the rule and its composite
(``_chunk_rule``); this is the same arithmetic with a chunk's working set
kept on the chip. The composite prepares 16 chunks at a time as batched
XLA products whose operands (the decay matrix, ``(I + A)^-1`` in ten
products, ``W``, ``U``: 64 x 64 and 64 x 256 float32 arrays) each travel
through HBM; here one grid step holds a tile of the sequence for one group
of value heads in VMEM, walks its chunks in order and carries the state
``S`` [d_k, d_v] in a VMEM scratch across the sequence, the last and
sequential grid axis.

Reads ``q``, ``k``, ``v`` where the mixer left them, ``[B, T, H * d]``
(the sequence in the sublanes, a head's features in the lanes), through
``BlockSpec``s of ``(1, tile, d)``: nothing is moved into another tiling
on the way in or out. The gates are 2 MiB each; the wrapper takes the
running sum of ``g`` inside each chunk and lays both out a chunk a row.

Value heads that share a key head share ``K K^T`` and ``Q K^T``, and a
64 x 64 float32 matrix fills half of a vector register's lanes and a
quarter of the 128 x 128 MXU. So an even group is handled two heads at a
time, SIDE BY SIDE in the lanes: ``[64, 128] = [X1 | X2]``. Element-wise
work then runs on full registers, and ``[X1 | X2] @ blockdiag(Y1, Y2) =
[X1 Y1 | X2 Y2]`` makes every product of the inverse one full-width
product. With an odd group the same code runs one head wide.

A chunk's state-free work is a chain of eleven dependent float32 products,
and the MXU takes products in program order: so four chunks are traced IN
STEP (``_Side.together``), each filling the others' waits, and only then
does the state walk through them.

What is float32 in the composite is float32 here (the norms, the decays,
``(I + A)^-1`` and its product with ``[beta exp(gamma) K | beta V]`` at
``Precision.HIGHEST``, the state and every accumulation); the six
products that take ``matmul_dtype`` operands there take them here.

Besides ``o`` the forward writes the state at every tile's start,
``f32[T / tile, B, hv, d_k, d_v]``: with the five inputs that is all the
backward needs. ``gdn_chunk_rule_bwd`` runs over the same grid with the
tiles from the last to the first and ``dS`` in a VMEM scratch. In a tile it
first runs the forward again from the saved state, which leaves every
chunk's start state, ``(I + A)^-1`` and ``[W | U]`` in VMEM (4.75 MiB at
heads of 128); then it walks the chunks in reverse. The gradient goes
through the solve in closed form (``dRHS = T^T d[W | U]``, ``dA = -dRHS [W |
U]^T`` below the diagonal: two float32 products, not the transposes of the
inverse's ten) and is float32 at ``Precision.HIGHEST`` wherever the
composite's backward is (the solve, the norms, the decays, ``dS``, every
accumulation); the products whose forward twins take ``matmul_dtype``
operands take them here (the composite's run at the default precision,
which on the TPU is one bf16 pass). It writes ``dq``, ``dk``, ``dv`` where
the inputs lie and the gates' gradients a chunk a row.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas as _pallas

__all__ = ["gdn_chunk_rule_fwd", "gdn_chunk_rule_bwd", "is_supported"]

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST
CHUNK = 64              # the one chunk size the kernel is written for
# q, k, v and o tiles, double-buffered, of the 16 MiB a kernel may ask for
_TILE_BYTES = 8 << 20
# The backward keeps more than those 16 MiB, the default scoped limit and not
# the chip's VMEM (128 MiB on a v5e): at Qwen3-Next's shapes 15.0 MiB of
# tiles and scratch (``_bwd_vmem_bytes``) and 4.3 MiB of the compiler's own,
# 19.29 MiB refused at the default (sandbox AOT, PR 35). It asks for what
# it reckons plus this headroom: 21 MiB + 16 at the most, since the
# forward's tiles (``_TILE_BYTES``) bound the backward's too.
_BWD_HEADROOM = 16 << 20
# Chunks traced in step (``_Side.together``), at the most: in the forward
# and the backward's forward sweep, and in the backward's reverse walk. At
# Qwen3-Next's 2 x 8192 x 32 heads on a v5e the forward takes 9.9 ms with
# one, 6.4 with two, 5.7 with four, and eight schedule no denser (PERF.md
# section 6, PR 31). The backward with its wrapper, (sweep, walk): (4, 4)
# 16.49 ms, (2, 4) 16.85, (4, 2) 17.37, (2, 2) 17.80, (4, 1) 18.54. A
# chunk in step is also a chunk more to trace, lower and compile, twice
# a run: four in the walk cost the cell's warm ``setup_s`` 2 s of its 52
# for 0.9 ms a call (PERF.md section 6, PR 35), so the walk takes two.
_AHEAD, _AHEAD_BACK = 4, 2


def _pack(hk, hv):
    """Value heads handled side by side: two where a key head serves an
    even number of them."""
    return 2 if (hv // hk) % 2 == 0 else 1


def is_supported(q_shape, v_shape, chunk, dtypes, matmul_dtype, mesh,
                 block_chunks) -> bool:
    """Whether the two kernels take this call, the forward and with it the
    backward: on a TPU (``_pallas._enabled()``), chunks of 64 tokens,
    ``d_k`` and ``d_v`` multiples of 128 whose tiles fit VMEM (the
    forward's bound the backward's: ``_BWD_HEADROOM``), key heads that
    divide the value heads, float32 or bf16 operands, and no multi-device
    mesh (a Mosaic kernel cannot sit under automatic partitioning, and no
    per-shard form is written). From shapes, dtypes, backend and mesh
    alone; ``block_chunks`` is the caller's tile, in chunks."""
    if not _pallas._enabled() or chunk != CHUNK:
        return False
    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    (hk, dk), (hv, dv) = q_shape[2:], v_shape[2:]
    if dk % 128 or dv % 128 or hv % hk:
        return False
    tile = block_chunks * chunk * 4 * 2
    if tile * (2 * dk + 2 * _pack(hk, hv) * dv) > _TILE_BYTES:
        return False
    floats = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    if any(jnp.dtype(d) not in floats for d in (*dtypes, matmul_dtype)):
        return False
    return mesh is None or mesh.devices.size == 1


def _bwd_vmem_bytes(nb, pack, dk, dv):
    """What ``gdn_chunk_rule_bwd`` holds in VMEM for a tile of ``nb`` chunks,
    float32 operands: the tiles of ``q``, ``k``, ``dq``, ``dk`` and of
    ``v``, ``do``, ``dv`` and the start state, double-buffered, and its
    scratch (``dS``, ``nb + 1`` chunk states, ``nb`` inverses and ``[W |
    U]``)."""
    tiles = nb * CHUNK * (4 * dk + 3 * pack * dv) + pack * dk * dv
    scratch = ((nb + 2) * pack * dk * dv + nb * CHUNK * pack * CHUNK
               + nb * pack * CHUNK * (dk + dv))
    return 4 * (2 * tiles + scratch)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _keep(mask, x, fill=0.0):
    """``x`` where ``mask``, of ``x``'s shape, and ``fill`` elsewhere.
    ``jnp.where`` is a jitted function of four equations: in kernels traced
    four chunks in step that is a quarter of the trace."""
    return jax.lax.select(mask, x, jnp.full_like(x, fill))


def _rsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


class _Side:
    """What both kernels say about one chunk of ``pack`` value heads SIDE BY
    SIDE in the lanes, ``[C, pack * C] = [X1 | X2]``: the masks, the moves
    between a chunk's row of gates and a column a head, the products, and
    what needs no state (``prepare``)."""

    def __init__(self, pack, mm):
        c_, width = CHUNK, pack * CHUNK
        self.pack, self.mm, self.shift = pack, mm, c_.bit_length() - 1
        self._same = {}
        self.row = jax.lax.broadcasted_iota(jnp.int32, (c_, width), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c_, width), 1)
        self.col, head = lane & (c_ - 1), lane >> self.shift
        self.heads = [head == p for p in range(pack)]       # a head's lanes
        self.lower, self.strict = self.row >= self.col, self.row > self.col
        self.eye = self.row == self.col
        self.eye_f = self.eye.astype(_F32)
        r2 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
        c2 = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
        self.own = (r2 >> self.shift) == (c2 >> self.shift)

    def same(self, size):
        if size not in self._same:
            s = size.bit_length() - 1
            self._same[size] = (self.row >> s) == (self.col >> s)
        return self._same[size]

    def diag(self, x):
        """[X1 | X2] -> blockdiag(X1, X2)."""
        return _keep(self.own, jnp.concatenate([x] * self.pack, axis=0))

    def fold(self, x):
        """The diagonal blocks of [pack * C, pack * C], side by side."""
        return self.stacked(_keep(self.own, x))

    def stacked(self, x):
        """The sum of [pack * C, n]'s row blocks, one a head."""
        return sum(x[p * CHUNK:(p + 1) * CHUNK] for p in range(self.pack))

    @staticmethod
    def exact(x, y, dims=((1,), (0,))):
        return jax.lax.dot_general(x, y, (dims, ((), ())), precision=_EXACT,
                                   preferred_element_type=_F32)

    def mmx(self, x, y):
        """[X1 Y1 | X2 Y2] in float32."""
        return self.exact(x, self.diag(y))

    def inverse(self, a):
        """``linear_attention._inverse_unit_lower`` on the heads side by
        side: the 8 x 8 diagonal blocks by their exact series, then block
        forward substitution, doubling the block. Yields after each product
        (``together`` says why)."""
        mmx, eye_f = self.mmx, self.eye_f
        d = _keep(self.same(8), a)
        d2 = mmx(d, d)
        yield
        inv = mmx(eye_f - d, eye_f + d2)
        d4 = mmx(d2, d2)
        yield
        inv, size = mmx(inv, eye_f + d4), 8
        while size < CHUNK:
            yield
            below = self.same(2 * size) & ~self.same(size) & self.strict
            step = mmx(inv, _keep(below, a))
            yield
            inv = inv - mmx(step, inv)
            size *= 2
        return inv

    def columns(self, x):
        """A chunk's row [1, pack * C] -> one [C, 1] column a head (the
        diagonal of the row spread over the sublanes, summed: exact)."""
        return self.rowsums(jnp.where(self.eye, x, 0.0))

    def rowsums(self, x):
        """[C, pack * C] -> each head's sum over its lanes, [C, 1]."""
        return [_rsum(_keep(lanes, x)) for lanes in self.heads]

    def beside(self, cols):
        """One [C, 1] column a head -> [C, pack * C], each over its lanes."""
        x = jnp.broadcast_to(cols[0], (CHUNK, self.pack * CHUNK))
        for lanes, col in zip(self.heads[1:], cols[1:]):
            x = jnp.where(lanes, col, x)
        return x

    def as_row(self, cols):
        """``columns`` back: one [C, 1] column a head -> [1, pack * C]."""
        return jnp.sum(_keep(self.eye, self.beside(cols)), axis=0,
                       keepdims=True)

    def dot(self, x, y, dims=((1,), (0,))):
        return jax.lax.dot_general(
            x.astype(self.mm), y.astype(self.mm), (dims, ((), ())),
            preferred_element_type=_F32)

    def gates(self, gam_ref, beta_ref, c):
        """Chunk ``c``'s ``gamma`` as its row and as a column a head,
        ``beta`` a column a head, and ``exp(gamma_i - gamma_j)`` where
        j <= i: masked BEFORE the exponential."""
        gam_row = gam_ref[0, 0, pl.ds(c, 1), :]
        gam = self.columns(gam_row)
        beta = self.columns(beta_ref[0, 0, pl.ds(c, 1), :])
        decay = jnp.exp(_keep(self.lower, self.beside(gam) - gam_row,
                              -jnp.inf))
        return gam, beta, decay

    def prepare(self, c, q_ref, k_ref, v_ref, gam_ref, beta_ref, dk, dv):
        """What needs no state, for chunk ``c`` of the tile (a generator:
        ``together``)."""
        c_, pack, mm, dot = CHUNK, self.pack, self.mm, self.dot
        rows = pl.ds(pl.multiple_of(c * c_, c_), c_)
        q = _l2norm(q_ref[0, rows, :].astype(_F32)) * dk ** -0.5
        k = _l2norm(k_ref[0, rows, :].astype(_F32))
        gam, beta, decay = self.gates(gam_ref, beta_ref, c)
        k_rep = jnp.concatenate([k.astype(mm)] * pack, axis=0)
        kk = dot(k, k_rep, ((1,), (1,)))                    # [K K^T | K K^T]
        qk = dot(q, k_rep, ((1,), (1,)))
        yield
        a = _keep(self.strict, self.beside(beta) * kk * decay)
        rhs = jnp.concatenate([jnp.concatenate(
            [k * (beta[p] * jnp.exp(gam[p])),
             v_ref[0, rows, p * dv:(p + 1) * dv].astype(_F32) * beta[p]],
            axis=1) for p in range(pack)], axis=0)          # [pack * C, dk+dv]
        inv = yield from self.inverse(a)
        yield
        wu = self.exact(self.diag(inv), rhs)
        aqk = self.diag(qk * decay).astype(mm)
        heads = []
        for p in range(pack):
            last = gam[p][c_ - 1:c_, :]                     # [1, 1]
            heads.append((
                wu[p * c_:(p + 1) * c_, :dk].astype(mm),
                wu[p * c_:(p + 1) * c_, dk:],
                (q * jnp.exp(gam[p])).astype(mm),
                (k * jnp.exp(last - gam[p])).astype(mm), jnp.exp(last)))
        return rows, aqk, heads, inv, wu

    @staticmethod
    def together(gens):
        """Generators run in step. A chunk's chain of eleven dependent
        float32 products is its critical path, and the MXU takes its
        products in program order: traced one chunk after the other, the
        second chunk's chain starts when the first one's ends. Each
        generator yields where a product's result is next needed, so the
        chunks' products alternate and one fills the other's waits."""
        live, done = list(gens), {}
        while live:
            for gen in list(live):
                try:
                    next(gen)
                except StopIteration as end:
                    done[gen] = end.value
                    live.remove(gen)
        return [done[gen] for gen in gens]


def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, st_ref, s_ref,
                *, nb, pack, dk, dv, mm, ahead):
    """One tile of ``nb`` chunks for ``pack`` value heads of one key head.
    ``gam_ref`` / ``beta_ref`` [1, 1, nb, pack * C]: a chunk a row, the
    heads side by side. ``s_ref`` [pack, dk, dv] carries the state. The
    chunks go ``ahead`` at a time: what needs no state in step for all of
    them, then the state through them in order."""
    c_ = CHUNK
    side = _Side(pack, mm)
    dot = side.dot

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
    st_ref[0, 0, 0] = s_ref[...]

    def advance(rows, aqk, heads, *_):
        """The four products with the state, and the state's step."""
        states = [s_ref[p] for p in range(pack)]
        vps = [u - dot(w, s) for (w, u, _, _, _), s in zip(heads, states)]
        inner = dot(aqk, jnp.concatenate(vps, axis=0))      # [pack * C, dv]
        for p, ((_, _, qg, kd, last), s, vp) in enumerate(
                zip(heads, states, vps)):
            o = dot(qg, s) + inner[p * c_:(p + 1) * c_]
            o_ref[0, rows, p * dv:(p + 1) * dv] = o.astype(o_ref.dtype)
            s_ref[p] = last * s + dot(kd, vp, ((0,), (0,)))

    def body(i, carry):
        for ready in side.together([
                side.prepare(i * ahead + j, q_ref, k_ref, v_ref, gam_ref,
                             beta_ref, dk, dv) for j in range(ahead)]):
            advance(*ready)
        return carry
    jax.lax.fori_loop(0, nb // ahead, body, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref,
                ds_ref, sc_ref, inv_ref, wu_ref,
                *, nb, pack, dk, dv, mm, ahead, back):
    """The backward of one tile, the tiles walked from the sequence's end:
    ``ds_ref`` [pack, dk, dv] carries ``dS``, the gradient of the state the
    tile hands on. ``st_ref`` is the tile's start state as the forward
    saved it. First the forward again, chunk by chunk, which leaves every
    chunk's start state (``sc_ref`` [nb + 1, pack, dk, dv]), ``(I + A)^-1``
    (``inv_ref`` [nb, C, pack * C]) and ``[W | U]`` (``wu_ref`` [nb,
    pack * C, dk + dv]) in VMEM; then the chunks in reverse, ``back`` at a
    time: what needs no ``dS`` in step (``recompute``), ``dS`` through them
    from the last to the first (``retreat``), and the gradients of the
    solve and of the element-wise part in step again (``finish``).
    ``dgam_ref`` / ``dbeta_ref`` as the gates, a chunk a row."""
    c_, scale = CHUNK, dk ** -0.5
    side = _Side(pack, mm)
    dot, exact = side.dot, side.exact
    t_, nt = ((0,), (0,)), ((1,), (1,))
    last_row = side.row[:, :1] == c_ - 1                    # [C, 1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
    sc_ref[0] = st_ref[0, 0, 0]

    def sweep(i, carry):
        chunks = [i * ahead + j for j in range(ahead)]
        ready = side.together([
            side.prepare(c, q_ref, k_ref, v_ref, gam_ref, beta_ref, dk, dv)
            for c in chunks])
        for c, (_, _, heads, inv, wu) in zip(chunks, ready):
            inv_ref[c], wu_ref[c] = inv, wu
            for p, (w, u, _, kd, last) in enumerate(heads):
                s = sc_ref[c, p]
                sc_ref[c + 1, p] = last * s + dot(kd, u - dot(w, s), t_)
        return carry
    jax.lax.fori_loop(0, nb // ahead, sweep, 0)

    def head_rows(x, p):
        return x[p * c_:(p + 1) * c_]

    def recompute(c):
        """Chunk ``c``'s element-wise quantities again, ``V' = U - W S`` and
        the products of ``dO`` that need no ``dS``."""
        rows = pl.ds(pl.multiple_of(c * c_, c_), c_)
        xq, xk = (r[0, rows, :].astype(_F32) for r in (q_ref, k_ref))
        rq, rk = (jax.lax.rsqrt(_rsum(x * x) + 1e-6) for x in (xq, xk))
        q, k = xq * rq * scale, xk * rk
        gam, beta, decay = side.gates(gam_ref, beta_ref, c)
        k_rep = jnp.concatenate([k.astype(mm)] * pack, axis=0)
        kk, qk = dot(k, k_rep, nt), dot(q, k_rep, nt)
        wu = wu_ref[c]
        do = jnp.concatenate([do_ref[0, rows, p * dv:(p + 1) * dv]
                              for p in range(pack)], axis=0).astype(mm)
        states = [sc_ref[c, p].astype(mm) for p in range(pack)]
        w = [head_rows(wu, p)[:, :dk].astype(mm) for p in range(pack)]
        vp = jnp.concatenate([head_rows(wu, p)[:, dk:] - dot(w[p], states[p])
                              for p in range(pack)], axis=0)
        yield
        aqk = qk * decay
        dvp = dot(side.diag(aqk), do, t_)                   # AQK^T dO
        daqk = side.fold(dot(do, vp, nt))                   # dO V'^T
        yield
        ds, dqg = [], []
        for p in range(pack):
            ds.append(dot(q * jnp.exp(gam[p]), head_rows(do, p), t_))
            dqg.append(dot(head_rows(do, p), states[p], nt))
        return dict(c=c, rows=rows, q=q, k=k, rq=rq, rk=rk, k_rep=k_rep,
                    gam=gam, beta=beta, decay=decay, kk=kk, aqk=aqk, w=w,
                    vp=vp, dvp=dvp, daqk=daqk, ds=ds, dqg=dqg)

    def retreat(m):
        """``dS`` steps back over the chunk: ``dV'`` whole, ``dKD`` and the
        gradient of ``exp(gamma_C)``, which read the ``dS`` that came in."""
        dvp, dkd, dlast = [], [], []
        for p in range(pack):
            ds, gam = ds_ref[p], m["gam"][p]
            last = gam[c_ - 1:c_, :]
            kd = m["k"] * jnp.exp(last - gam)
            dvp.append(head_rows(m["dvp"], p) + dot(kd, ds))
            dkd.append(dot(head_rows(m["vp"], p), ds, nt))
            dlast.append(jnp.sum(_rsum(sc_ref[m["c"], p] * ds), axis=0,
                                 keepdims=True))
            ds_ref[p] = (m["ds"][p] + jnp.exp(last) * ds
                         - dot(m["w"][p], dvp[p], t_))
        return dvp, dkd, dlast

    def finish(m, dvp, dkd, dlast):
        """Through the solve in closed form (``[W | U] = T RHS``, ``T = (I +
        A)^-1``: ``dRHS = T^T d[W | U]`` and ``dA = -T^T (d[W | U] RHS^T)
        T^T = -dRHS [W | U]^T`` below the diagonal), then the element-wise
        part back to ``q``, ``k``, ``v`` and the gates."""
        c, rows, q, k = m["c"], m["rows"], m["q"], m["k"]
        gam, beta, decay = m["gam"], m["beta"], m["decay"]
        dwu = jnp.concatenate([jnp.concatenate(
            [-dot(dvp[p], sc_ref[c, p], nt), dvp[p]], axis=1)
            for p in range(pack)], axis=0)                  # d[W | U]
        yield
        drhs = exact(side.diag(inv_ref[c]), dwu, t_)
        yield
        da = -_keep(side.strict, side.fold(exact(drhs, wu_ref[c], nt)))
        yield
        bb, kkd = side.beside(beta), m["kk"] * decay
        both = da * (bb * kkd) + m["daqk"] * m["aqk"]       # d decay x decay
        dkk, dqk = da * bb * decay, m["daqk"] * decay
        dbeta, dgam = side.rowsums(da * kkd), side.rowsums(both)
        dkh = dot(dkk, m["k_rep"]) + side.stacked(
            dot(dkk, k, t_) + dot(dqk, q, t_))
        dqh = dot(dqk, m["k_rep"])
        yield
        for p in range(pack):
            eg = jnp.exp(gam[p])
            bg = beta[p] * eg
            last = gam[p][c_ - 1:c_, :]
            drk, drv = head_rows(drhs, p)[:, :dk], head_rows(drhs, p)[:, dk:]
            lanes = slice(p * dv, (p + 1) * dv)
            dv_ref[0, rows, lanes] = (drv * beta[p]).astype(dv_ref.dtype)
            kdot = _rsum(drk * k)
            dbeta[p] += kdot * eg + _rsum(
                drv * v_ref[0, rows, lanes].astype(_F32))
            ek = jnp.exp(last - gam[p])
            back = _rsum(dkd[p] * k) * ek       # through exp(gamma_C - gamma)
            at_last = (jnp.sum(back, axis=0, keepdims=True)
                       + dlast[p] * jnp.exp(last))
            dgam[p] += (kdot * bg + _rsum(m["dqg"][p] * q) * eg - back
                        + jnp.where(last_row, at_last, 0.0))
            dkh += drk * bg + dkd[p] * ek
            dqh += m["dqg"][p] * eg
        # back through the two norms: x r -> r (dy - y sum(dy y))
        dq_ref[0, rows, :] = (m["rq"] * (dqh * scale - q * (
            _rsum(dqh * q) / scale))).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (m["rk"] * (dkh - k * _rsum(dkh * k))).astype(
            dk_ref.dtype)
        dgam_ref[0, 0, pl.ds(c, 1), :] = (
            side.as_row(dgam) - jnp.sum(both, axis=0, keepdims=True))
        dbeta_ref[0, 0, pl.ds(c, 1), :] = side.as_row(dbeta)

    def walk(i, carry):
        first = (nb // back - 1 - i) * back
        ready = side.together([recompute(first + j) for j in range(back)])
        stepped = [retreat(m) for m in reversed(ready)][::-1]
        side.together([finish(m, *s) for m, s in zip(ready, stepped)])
        return carry
    jax.lax.fori_loop(0, nb // back, walk, 0)


def _ahead(nb, most):
    """Chunks of a tile of ``nb`` traced in step: ``most``, or the most
    under it that divides ``nb``."""
    return next(n for n in range(most, 0, -1) if nb % n == 0)


def _tiling(q, v, g, beta, block_chunks):
    """How both kernels see a call: the sizes, ``flat`` ([B, T, H, d] ->
    [B, T', H * d]: no move), the gates a chunk a row (``rows`` and back,
    ``unrows``), the running sum of ``g`` inside each chunk, and the specs
    over the grid ``(batch, group of pack value heads, tile)`` whose tile
    index is ``tile_of(t_)``. A ``T`` that is no whole number of tiles is
    padded as the composite pads it: a padded token decays nothing (``g``
    0) and writes nothing (``beta``, ``k`` 0)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    pack = _pack(hk, hv)
    nb = min(block_chunks, -(-t // CHUNK))
    tile = nb * CHUNK
    pad = -t % tile
    tiles, chunks = (t + pad) // tile, (t + pad) // CHUNK
    groups, r = hv // pack, hv // hk

    def flat(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, t + pad, -1)

    def rows(x):            # [B, T', hv] -> [B, hv / pack, chunks, pack * C]
        x = x.reshape(b, chunks, CHUNK, groups, pack)
        return x.transpose(0, 3, 1, 4, 2).reshape(b, groups, chunks,
                                                  pack * CHUNK)

    def unrows(x):          # and back, [B, chunks, C, hv]
        x = x.reshape(b, groups, chunks, pack, CHUNK)
        return x.transpose(0, 2, 4, 1, 3).reshape(b, chunks, CHUNK, hv)
    g, beta = (jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0)))
               for x in (g, beta))
    gamma = jnp.cumsum(g.reshape(b, chunks, CHUNK, hv), axis=2)

    def specs(tile_of):
        def seq(width, head):
            return pl.BlockSpec(
                (1, tile, width),
                lambda b_, p_, t_: (b_, tile_of(t_), head(p_)))
        return dict(
            key=seq(dk, lambda p_: p_ * pack // r),     # the group's key head
            own_key=seq(dk, lambda p_: p_),
            value=seq(pack * dv, lambda p_: p_),
            gate=pl.BlockSpec((1, 1, nb, pack * CHUNK),
                              lambda b_, p_, t_: (b_, p_, tile_of(t_), 0)),
            state=pl.BlockSpec(
                (1, 1, 1, pack, dk, dv),
                lambda b_, p_, t_: (tile_of(t_), b_, p_, 0, 0, 0)))
    return types.SimpleNamespace(
        b=b, t=t, hk=hk, dk=dk, hv=hv, dv=dv, pack=pack, nb=nb, pad=pad,
        tiles=tiles, chunks=chunks, groups=groups, flat=flat, unrows=unrows,
        gamma=rows(gamma), beta=rows(beta), specs=specs)


@functools.partial(jax.jit, static_argnames=("mm", "block_chunks"))
def gdn_chunk_rule_fwd(q, k, v, g, beta, *, mm, block_chunks):
    """``o`` [B, T, hv, d_v] in ``v``'s dtype and the state at the start of
    every tile of ``block_chunks`` chunks, ``f32[tiles, B, hv, d_k, d_v]``,
    of the gated delta rule in chunks of 64 tokens from a zero state.
    ``q``, ``k`` [B, T, hk, d_k], ``v`` [B, T, hv, d_v], ``g``, ``beta``
    [B, T, hv]; ``mm`` is the matrix products' operand dtype. ``T`` is
    padded to whole tiles (``_tiling``)."""
    n = _tiling(q, v, g, beta, block_chunks)
    spec = n.specs(lambda t_: t_)
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, nb=n.nb, pack=n.pack, dk=n.dk,
                          dv=n.dv, mm=jnp.dtype(mm),
                          ahead=_ahead(n.nb, _AHEAD)),
        grid=(n.b, n.groups, n.tiles),
        in_specs=[spec["key"], spec["key"], spec["value"], spec["gate"],
                  spec["gate"]],
        out_specs=[spec["value"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct((n.b, n.t + n.pad, n.hv * n.dv), v.dtype),
            jax.ShapeDtypeStruct(
                (n.tiles, n.b, n.groups, n.pack, n.dk, n.dv), _F32)],
        scratch_shapes=[pltpu.VMEM((n.pack, n.dk, n.dv), _F32)],
        name="gdn_chunk_rule_fwd",
        interpret=_pallas._interpret(),
    )(n.flat(q), n.flat(k), n.flat(v), n.gamma, n.beta)
    return (o[:, :n.t].reshape(n.b, n.t, n.hv, n.dv),
            states.reshape(n.tiles, n.b, n.hv, n.dk, n.dv))


@functools.partial(jax.jit, static_argnames=("mm", "block_chunks"))
def gdn_chunk_rule_bwd(q, k, v, g, beta, states, do, *, mm, block_chunks):
    """The gradients of ``gdn_chunk_rule_fwd``'s ``o`` with respect to its
    five inputs, each in its input's shape and dtype, from ``o``'s cotangent
    ``do`` [B, T, hv, d_v] and the ``states`` that call saved. One kernel
    over the same grid, the tiles walked from the last to the first; a key
    head's ``dq`` and ``dk`` are summed inside it over the ``pack`` value
    heads of a group, and here over the groups that share the head."""
    n = _tiling(q, v, g, beta, block_chunks)
    b, t, rows, nb, pack, dk, dv = (n.b, n.t, n.t + n.pad, n.nb, n.pack,
                                    n.dk, n.dv)
    spec = n.specs(lambda t_: n.tiles - 1 - t_)
    shared = n.groups // n.hk       # groups that share a key head
    key_out = jax.ShapeDtypeStruct((b, rows, n.groups * dk),
                                   q.dtype if shared == 1 else _F32)
    gate_out = jax.ShapeDtypeStruct((b, n.groups, n.chunks, pack * CHUNK),
                                    _F32)
    dq, dk_, dv_, dgamma, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, nb=nb, pack=pack, dk=dk, dv=dv,
                          mm=jnp.dtype(mm), ahead=_ahead(nb, _AHEAD),
                          back=_ahead(nb, _AHEAD_BACK)),
        grid=(b, n.groups, n.tiles),
        in_specs=[spec["key"], spec["key"], spec["value"], spec["gate"],
                  spec["gate"], spec["state"], spec["value"]],
        out_specs=[spec["own_key"], spec["own_key"], spec["value"],
                   spec["gate"], spec["gate"]],
        out_shape=[key_out, key_out,
                   jax.ShapeDtypeStruct((b, rows, n.hv * dv), v.dtype),
                   gate_out, gate_out],
        scratch_shapes=[pltpu.VMEM((pack, dk, dv), _F32),
                        pltpu.VMEM((nb + 1, pack, dk, dv), _F32),
                        pltpu.VMEM((nb, CHUNK, pack * CHUNK), _F32),
                        pltpu.VMEM((nb, pack * CHUNK, dk + dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_bytes(nb, pack, dk, dv)
            + _BWD_HEADROOM),
        name="gdn_chunk_rule_bwd",
        interpret=_pallas._interpret(),
    )(n.flat(q), n.flat(k), n.flat(v), n.gamma, n.beta,
      states.reshape(n.tiles, b, n.groups, pack, dk, dv), n.flat(do))

    def key_grad(x, like):
        x = x[:, :t].reshape(b, t, n.hk, shared, dk)
        return (x[:, :, :, 0] if shared == 1 else x.sum(axis=3)).astype(
            like.dtype)

    def gate_grad(x, like):
        return x.reshape(b, rows, n.hv)[:, :t].astype(like.dtype)
    # gamma is the running sum of g inside a chunk: dg_i = sum_{j >= i}
    dg = jax.lax.cumsum(n.unrows(dgamma), axis=2, reverse=True)
    return (key_grad(dq, q), key_grad(dk_, k),
            dv_[:, :t].reshape(b, t, n.hv, dv), gate_grad(dg, g),
            gate_grad(n.unrows(dbeta), beta))

"""Hypothesis property tests on the system's invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import core as tdp
from repro.core import Field, Lattice
from repro.kernels import ref
from repro.models.config import plan_layer_groups, repeat_program, BLOCK_TYPES
from repro.optim import dequantize_blockwise, quantize_blockwise

SET = settings(max_examples=25, deadline=None)


@st.composite
def lattice_and_vvl(draw):
    dims = draw(st.lists(st.integers(2, 9), min_size=1, max_size=3))
    vvl = draw(st.sampled_from([4, 8, 16, 32]))
    return Lattice(tuple(dims)), vvl


class TestTdpProperties:
    @SET
    @given(lattice_and_vvl(), st.floats(-3, 3))
    def test_launch_padding_never_pollutes(self, lat_vvl, a):
        """Padding sites must never leak into outputs for ANY lattice/VVL."""
        lat, vvl = lat_vvl

        @tdp.site_kernel
        def affine(x, a=1.0):
            return a * x + 1.0

        rng = np.random.default_rng(lat.nsites)
        x = jnp.asarray(rng.normal(size=(2, lat.nsites)), jnp.float32)
        y = tdp.launch(affine, lat, [x], consts={"a": a}, vvl=vvl)
        np.testing.assert_allclose(y, a * x + 1.0, rtol=1e-5, atol=1e-5)

    @SET
    @given(lattice_and_vvl())
    def test_reduce_sum_matches_numpy(self, lat_vvl):
        lat, vvl = lat_vvl

        @tdp.site_kernel
        def ident(x):
            return x

        rng = np.random.default_rng(lat.nsites + 1)
        x = jnp.asarray(rng.normal(size=(3, lat.nsites)), jnp.float32)
        got = tdp.reduce(ident, lat, [x], op="sum", vvl=vvl)
        np.testing.assert_allclose(got, np.asarray(x).sum(-1), rtol=1e-4)

    @SET
    @given(st.integers(1, 64), st.integers(1, 5))
    def test_masked_copy_partition(self, nsites, ncomp):
        """Masked copy of M ∪ masked copy of ¬M == full copy."""
        from repro.core import (copy_from_target_masked, copy_to_target)
        lat = Lattice((nsites,))
        rng = np.random.default_rng(nsites * ncomp)
        f = Field(lat, ncomp, np.float32)
        f.data[...] = rng.normal(size=f.array_shape)
        t = copy_to_target(f)
        mask = rng.random(nsites) < 0.5
        a = Field(lat, ncomp, np.float32)
        copy_from_target_masked(t, mask, a)
        copy_from_target_masked(t, ~mask, a)
        np.testing.assert_allclose(a.data, f.data, rtol=1e-6)


class TestLayoutProperties:
    """SoA ↔ AoSoA transform invariants (repro/core/layout.py) over
    arbitrary component counts, site counts, and inner widths — including
    remainder blocks (vvl ∤ nsites) and vvl > nsites.  The enumerated
    fallback runs without hypothesis in
    test_layout.py::TestTransforms."""

    @SET
    @given(st.integers(1, 6),             # ncomp
           st.integers(1, 200),           # nsites (odd, prime, tiny...)
           st.integers(1, 64),            # vvl (any, incl. > nsites)
           st.integers(0, 2))             # extra leading batch dims
    def test_roundtrip_exact(self, ncomp, nsites, vvl, nlead):
        from repro.core.layout import (aosoa_nblocks, aosoa_to_soa,
                                       soa_to_aosoa)
        rng = np.random.default_rng(ncomp * 1000 + nsites * 10 + vvl)
        lead = (2,) * nlead
        x = jnp.asarray(rng.normal(size=(*lead, ncomp, nsites)),
                        jnp.float32)
        y = soa_to_aosoa(x, vvl)
        nblk = aosoa_nblocks(nsites, vvl)
        assert y.shape == (nblk, *lead, ncomp, vvl)
        np.testing.assert_array_equal(
            np.asarray(aosoa_to_soa(y, nsites)), np.asarray(x))
        # remainder lanes are zero-padded, never garbage
        pad = nblk * vvl - nsites
        if pad:
            flat = np.moveaxis(np.asarray(y), 0, -2)  # (..., ncomp, nblk, vvl)
            tail = flat.reshape(*lead, ncomp, nblk * vvl)[..., nsites:]
            np.testing.assert_array_equal(tail, 0.0)

    @SET
    @given(st.integers(1, 4),             # ncomp
           st.integers(1, 8),             # nplanes
           st.integers(2, 40),            # plane site count
           st.integers(1, 16))            # vvl candidate
    def test_plane_roundtrip_or_named_error(self, ncomp, npl, rn, vvl):
        """plane_to_aosoa either round-trips exactly (vvl | plane sites)
        or refuses with the no-remainder-blocks error — never silently
        truncates."""
        from repro.core.layout import plane_from_aosoa, plane_to_aosoa
        rng = np.random.default_rng(ncomp + npl * 10 + rn * 100 + vvl)
        x = jnp.asarray(rng.normal(size=(ncomp, npl, rn)), jnp.float32)
        if rn % vvl:
            with pytest.raises(ValueError, match="no remainder blocks"):
                plane_to_aosoa(x, vvl)
            return
        y = plane_to_aosoa(x, vvl)
        assert y.shape == (npl, rn // vvl, ncomp, vvl)
        np.testing.assert_array_equal(
            np.asarray(plane_from_aosoa(y, (rn,))), np.asarray(x))

    @SET
    @given(st.integers(1, 5),             # ncomp
           st.integers(1, 120),           # nsites
           st.sampled_from([1, 2, 4, 8, 16]),
           st.floats(-2, 2))
    def test_gathered_layouts_agree(self, ncomp, nsites, vvl, a):
        """One pointwise launch, every layout×vvl: the same results under
        the cross-path contract."""
        from repro import tdp
        rng = np.random.default_rng(nsites * 10 + ncomp)
        x = jnp.asarray(rng.normal(size=(ncomp, nsites)), jnp.float32)
        spec = tdp.KernelSpec(lambda v, a=1.0: a * v + 1.0,
                              fields=(tdp.FieldSpec(ncomp=ncomp),),
                              out=ncomp, name=f"affine_{ncomp}")
        base = tdp.launch(spec, tdp.Target("xla"), x, a=a)
        for layout in tdp.LAYOUTS:
            t = tdp.Target("xla", vvl=vvl, layout=layout)
            tdp.check_fields([base], [tdp.launch(spec, t, x, a=a)])


class TestExchangeProperties:
    """The generalized ghost exchange (repro/core/program.py) against a
    wrap-indexed global reference — any dim, any hop count, widths wider
    than the pencil thickness.  The enumerated fallback (same machinery,
    fixed cases) runs without hypothesis in
    test_program.py::TestPencilExchange."""

    @SET
    @given(st.integers(2, 6),            # nranks
           st.integers(1, 4),            # local extent (1 = thin pencil)
           st.integers(1, 7),            # requested width
           st.integers(1, 3),            # ncomp
           st.integers(0, 1))            # which grid dim is exchanged
    def test_exchange_matches_wrap_indexed_global(self, nranks, loc,
                                                  width, ncomp, dim):
        import importlib
        P = importlib.import_module("repro.core.program")
        glob = nranks * loc
        width = min(width, glob - 1)     # the compile-time width bound
        other = 3                        # extent of the unexchanged dim
        shape = (ncomp, other, glob) if dim == 1 else (ncomp, glob, other)
        rng = np.random.default_rng(nranks * 100 + loc * 10 + width)
        g = rng.normal(size=shape).astype(np.float32)
        ax = dim + 1
        shards = jnp.asarray(np.stack(
            [np.take(g, np.arange(i * loc, (i + 1) * loc), axis=ax)
             for i in range(nranks)]))

        def permute(x, pairs):
            idx = np.zeros(nranks, int)
            for src, dst in pairs:
                idx[dst] = src
            return x[jnp.asarray(idx)]

        # shard dim d is axis d+2 of the stack; exchange_ghosts slices
        # axis dim+1, so shift dim past the rank axis
        got = np.asarray(P.exchange_ghosts(shards, dim + 1, width,
                                           nranks, permute))
        hops = P._exchange_hops(width, loc)
        assert hops[-1][0] == -(-width // loc)
        assert sum(t for _, t in hops) == width
        for i in range(nranks):
            want = np.take(g, np.arange(i * loc - width,
                                        (i + 1) * loc + width) % glob,
                           axis=ax)
            np.testing.assert_array_equal(got[i], want)


class TestAutotuneProperties:
    """Invariants of ``tdp.autotune``'s space construction
    (repro/core/autotune.py)."""

    @staticmethod
    def _star_spec(ndim, radius):
        """A radius-``radius`` axis star stencil spec (1-component)."""
        from repro.core import FieldSpec, KernelSpec, Stencil
        offs = [(0,) * ndim]
        for d in range(ndim):
            for k in range(1, radius + 1):
                for sign in (1, -1):
                    o = [0] * ndim
                    o[d] = sign * k
                    offs.append(tuple(o))
        stc = Stencil(f"star{ndim}d_r{radius}", tuple(offs))
        return KernelSpec(lambda p: p.sum(0, keepdims=True),
                          fields=(FieldSpec(ncomp=1, stencil=stc),),
                          out=(1,), name=f"star_r{radius}")

    @SET
    @given(st.lists(st.integers(4, 24), min_size=1, max_size=3),
           st.integers(1, 2),
           st.sampled_from([0, 2 ** 14, 2 ** 20]))
    def test_plane_block_space_divides_and_fits(self, dims, radius,
                                                vmem_limit):
        """Every emitted plane_block divides the launch's (extended)
        plane count AND passes the vmem_bytes_estimate() filter; every
        divisor is either emitted or pruned with a vmem reason."""
        from repro import tdp
        shape = tuple(dims)
        spec = self._star_spec(len(shape), radius)
        lat = Lattice(shape)
        tgt = tdp.Target("pallas_windowed", interpret=True)
        feasible, pruned = tdp.plane_block_candidates(
            spec, tgt, lat, vmem_limit=vmem_limit)
        nplanes = tdp.launch_plan(spec, tgt, lattice=lat).shape[0]
        assert nplanes == shape[0]
        for p in feasible:
            assert nplanes % p == 0
            plan = tdp.launch_plan(spec, tgt.with_tuning(plane_block=p),
                                   lattice=lat)
            assert plan.vmem_bytes_estimate() <= vmem_limit
        emitted = set(feasible) | {v for v, _ in pruned}
        assert emitted == {d for d in range(1, nplanes + 1)
                           if nplanes % d == 0}
        for v, why in pruned:
            assert "vmem estimate" in why
        # the estimate never undercounts: at least two pipeline buffers
        # plus the loaded value of the raw window and output bytes
        ext_rest = math.prod(d + 2 * radius for d in shape[1:])
        for p in emitted:
            plan = tdp.launch_plan(spec, tgt.with_tuning(plane_block=p),
                                   lattice=lat)
            raw = ((p + 2 * radius) * ext_rest
                   + p * math.prod(shape[1:])) * 4
            assert plan.vmem_bytes_estimate() >= 3 * raw

    @SET
    @given(st.dictionaries(
        st.sampled_from(["plane_block", "block_f", "block_q", "vjp"]),
        st.integers(1, 512), max_size=4),
        st.permutations(["plane_block", "block_f", "block_q", "vjp"]))
    def test_with_tuning_round_trips_freeze_and_hash(self, tuning, order):
        """Equal tuning ⇒ equal Target ⇒ equal hash (the plan-cache-key
        contract), regardless of knob insertion order."""
        from repro import tdp
        base = tdp.Target("pallas_windowed", interpret=True)
        a = base.with_tuning(tuning)
        b = base
        for k in order:                       # knob-at-a-time, any order
            if k in tuning:
                b = b.with_tuning({k: tuning[k]})
        assert a == b
        assert hash(a) == hash(b)
        assert a.tuning_dict() == dict(tuning)
        # merge preserves unrelated knobs; replace-spelling drops them
        c = a.with_tuning(extra=7)
        assert c.tuning_dict() == {**tuning, "extra": 7}
        assert a.with_(tuning={"extra": 7}).tuning_dict() == {"extra": 7}


class TestAttentionProperties:
    @SET
    @given(st.integers(2, 24), st.integers(1, 4), st.booleans())
    def test_causality(self, s, h, use_window):
        """Output at position t never depends on inputs at positions > t."""
        rng = np.random.default_rng(s * h)
        q = jnp.asarray(rng.normal(size=(1, h, s, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, h, s, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, h, s, 8)), jnp.float32)
        window = 4 if use_window else 0
        base = ref.attention_ref(q, k, v, causal=True, window=window)
        t = s // 2
        k2 = k.at[:, :, t + 1:].set(99.0)
        v2 = v.at[:, :, t + 1:].set(-99.0)
        pert = ref.attention_ref(q, k2, v2, causal=True, window=window)
        np.testing.assert_allclose(base[:, :, :t + 1], pert[:, :, :t + 1],
                                   rtol=1e-5, atol=1e-5)

    @SET
    @given(st.integers(8, 64), st.sampled_from([4, 8, 16]))
    def test_chunked_equals_ref_any_blocking(self, s, bq):
        rng = np.random.default_rng(s + bq)
        q = jnp.asarray(rng.normal(size=(1, 2, s, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, s, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, s, 8)), jnp.float32)
        a = ref.attention_ref(q, k, v, causal=True)
        b = ref.attention_chunked_ref(q, k, v, causal=True, block_q=bq)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    @SET
    @given(st.floats(1.0, 100.0))
    def test_softcap_bounds_scores(self, cap):
        """Softcapped attention == attention over tanh-bounded scores; the
        output stays a convex combination of V rows."""
        rng = np.random.default_rng(int(cap * 7))
        q = jnp.asarray(10 * rng.normal(size=(1, 1, 8, 4)), jnp.float32)
        k = jnp.asarray(10 * rng.normal(size=(1, 1, 8, 4)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, 8, 4)), jnp.float32)
        out = ref.attention_ref(q, k, v, causal=False, softcap=float(cap))
        vmin, vmax = np.asarray(v).min(), np.asarray(v).max()
        assert (np.asarray(out) >= vmin - 1e-5).all()
        assert (np.asarray(out) <= vmax + 1e-5).all()


class TestQuantProperties:
    @SET
    @given(st.integers(1, 500), st.sampled_from([16, 64, 256]),
           st.floats(1e-3, 1e3))
    def test_error_bound(self, n, block, scale):
        """Global bound: |x - deq(quant(x))| ≤ max|x|/127 elementwise
        (each block's error ≤ its own absmax/127 ≤ the global one)."""
        rng = np.random.default_rng(n + block)
        x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
        xr = dequantize_blockwise(quantize_blockwise(x, block), x.shape)
        bound = float(jnp.abs(x).max()) / 127.0 * 1.01 + 1e-9
        assert float(jnp.abs(x - xr).max()) <= bound


class TestLayerProgramProperties:
    @SET
    @given(st.lists(st.sampled_from(["attn", "local", "mamba2"]),
                    min_size=1, max_size=6),
           st.integers(1, 80))
    def test_groups_always_cover(self, pattern, n):
        prog = repeat_program(tuple(pattern), n)
        rebuilt = []
        for unit, k in plan_layer_groups(prog):
            rebuilt.extend(list(unit) * k)
        assert tuple(rebuilt) == prog


class TestMoEProperties:
    @SET
    @given(st.integers(2, 32), st.integers(2, 8), st.integers(1, 4))
    def test_capacity_equals_dense_when_generous(self, t, e, k):
        """cap ≥ T ⇒ dropless ⇒ exactly the dense one-hot computation."""
        if k > e:
            k = e
        from repro.models.moe import _apply_experts_capacity
        from repro.models.config import (ModelConfig, AttnConfig, MoEConfig,
                                         repeat_program)
        from repro.models.context import ExecContext
        cfg = ModelConfig(
            name="p", d_model=8, n_layers=1, vocab_size=32, d_ff=16,
            layer_program=("attn_moe",), attn=AttnConfig(1, 1, 8),
            moe=MoEConfig(num_experts=e, top_k=k, d_expert=8))
        rng = np.random.default_rng(t * e + k)
        xs = jnp.asarray(rng.normal(size=(t, 8)), jnp.float32)
        e_ids = jnp.asarray(rng.integers(0, e, (t,)), jnp.int32)
        p = {"w_up": jnp.asarray(rng.normal(size=(e, 8, 8)), jnp.float32),
             "w_gate": jnp.asarray(rng.normal(size=(e, 8, 8)), jnp.float32),
             "w_down": jnp.asarray(rng.normal(size=(e, 8, 8)), jnp.float32)}
        got = _apply_experts_capacity(xs, e_ids, jnp.ones((t,), bool), p,
                                      cfg, ExecContext(), cap=t)
        # dense reference
        we = np.asarray(p["w_up"])[np.asarray(e_ids)]
        wg = np.asarray(p["w_gate"])[np.asarray(e_ids)]
        wd = np.asarray(p["w_down"])[np.asarray(e_ids)]
        up = np.einsum("td,tdf->tf", np.asarray(xs), we)
        gate = np.einsum("td,tdf->tf", np.asarray(xs), wg)
        act = gate * (1 / (1 + np.exp(-gate))) * up
        want = np.einsum("tf,tfd->td", act, wd)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                                   atol=2e-4)


class TestDataProperties:
    @SET
    @given(st.integers(0, 1000), st.integers(2, 16))
    def test_any_slice_matches_full(self, step, batch):
        from repro.data import SyntheticConfig, batch_for_step
        cfg = SyntheticConfig(vocab_size=50, seq_len=8, global_batch=batch,
                              seed=3)
        full = batch_for_step(cfg, step)
        lo = batch // 3
        hi = max(lo + 1, 2 * batch // 3)
        part = batch_for_step(cfg, step, lo=lo, hi=hi)
        np.testing.assert_array_equal(full["tokens"][lo:hi], part["tokens"])

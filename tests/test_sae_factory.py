"""The sparse-SAE training factory, locked down.

* property tests (hypothesis, or the deterministic ``_hypothesis_compat``
  fallback in the seed container): harvest round-trip — shapes/dtypes/layer
  selection survive shard-write → ``DataPipeline``-read — and the MMCS
  invariants (self-similarity, permutation/sign invariance, symmetry).
* a deterministic tiny-config regression for ``repro.training.sae_tables`` that
  pins test accuracy and first-layer column sparsity for all 5 methods.
* a miniature end-to-end factory run (harvest → projected SAE training →
  MMCS) asserting the per-step constraint actually holds on the result.
* GSP whole-network sparsification through the mesh executor on a forced
  8-device CPU mesh (subprocess — device count is fixed at startup).
"""

import json
import re
import subprocess
import sys

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # seed container: deterministic fallback, tests still run
    from _hypothesis_compat import given, settings, st

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import DataConfig, DataPipeline
from repro.data.activations import ActivationReader, read_meta
from repro.training import sae_factory as F
from repro.training.mmcs import mmcs, mmcs_sym, mmcs_table


FCFG = F.SAEFactoryConfig(layers=(0, 2), harvest_steps=3, seq_len=8,
                          lm_batch=2, train_steps=6, sae_batch=16,
                          microbatch=8, expansion=2, radius=0.2)


@pytest.fixture(scope="module")
def harvest_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("harvest")
    meta = F.harvest_activations(FCFG, d)
    return d, meta


def _rand(shape, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


# ----------------------------------------------------------- harvest round-trip
class TestHarvestRoundTrip:
    def test_meta(self, harvest_dir):
        d, meta = harvest_dir
        assert meta["layers"] == [0, 2]
        assert meta["site"] == "resid"
        assert meta["rows_per_shard"] == FCFG.lm_batch * FCFG.seq_len
        assert meta["n_shards"] == FCFG.harvest_steps
        assert read_meta(d) == meta

    def test_only_selected_layers_on_disk(self, harvest_dir):
        d, _ = harvest_dir
        layers = sorted({int(m.group(1)) for p in d.glob("layer*_shard*.npy")
                         for m in [re.match(r"layer(\d+)_shard", p.name)]})
        assert layers == [0, 2]

    @given(step=st.integers(0, 40), layer=st.sampled_from([0, 2]))
    @settings(max_examples=10, deadline=None)
    def test_reader_shapes_dtype_wraparound(self, harvest_dir, step, layer):
        d, meta = harvest_dir
        reader = ActivationReader(d, DataConfig(
            vocab=1, seq_len=0, global_batch=8, microbatch=4,
            activation_dir=str(d), activation_layer=layer))
        b = reader.batch(step)
        assert b.shape == (8, meta["d_model"])
        assert str(b.dtype) == meta["dtype"]
        # stateless cursor: same step -> identical rows; wrap-around is modular
        np.testing.assert_array_equal(b, reader.batch(step))
        n_rows = meta["rows_per_shard"] * meta["n_shards"]
        np.testing.assert_array_equal(
            b, reader.batch(step + n_rows // 8))

    def test_pipeline_microbatch_layout(self, harvest_dir):
        d, meta = harvest_dir
        pipe = DataPipeline(DataConfig(
            vocab=1, seq_len=0, global_batch=8, microbatch=4,
            activation_dir=str(d), activation_layer=0))
        b = pipe.batch(0)
        assert b.shape == (2, 4, meta["d_model"])
        flat = np.asarray(b).reshape(8, meta["d_model"])
        raw = ActivationReader(d, DataConfig(
            vocab=1, seq_len=0, global_batch=8, microbatch=4,
            activation_dir=str(d), activation_layer=0)).batch(0)
        np.testing.assert_array_equal(flat, raw)

    def test_layer_selection_distinct(self, harvest_dir):
        d, _ = harvest_dir
        def rows(layer):
            return ActivationReader(d, DataConfig(
                vocab=1, seq_len=0, global_batch=8, microbatch=4,
                activation_dir=str(d), activation_layer=layer)).batch(0)
        assert float(np.abs(rows(0) - rows(2)).max()) > 1e-6

    def test_mlp_site_differs_from_resid(self, tmp_path):
        import dataclasses
        fcfg = dataclasses.replace(FCFG, site="mlp", layers=(0,),
                                   harvest_steps=1)
        meta = F.harvest_activations(fcfg, tmp_path)
        assert meta["site"] == "mlp"
        assert meta["d_model"] > 0


# ------------------------------------------------------------- MMCS invariants
class TestMMCSInvariants:
    @given(d=st.integers(4, 24), k=st.integers(2, 24),
           seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_self_similarity_is_one(self, d, k, seed):
        a = _rand((d, k), seed, scale=2.0)
        assert float(mmcs(a, a)) == pytest.approx(1.0, abs=1e-5)

    @given(d=st.integers(4, 24), k=st.integers(2, 24),
           seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_permutation_and_sign_invariance(self, d, k, seed):
        a = _rand((d, k), seed, scale=2.0)
        rng = np.random.default_rng(seed)
        perm = rng.permutation(k)
        signs = jnp.asarray(rng.choice([-1.0, 1.0], size=k), jnp.float32)
        b = a[:, perm] * signs[perm]
        assert float(mmcs(a, b)) == pytest.approx(1.0, abs=1e-5)
        assert float(mmcs_sym(a, b)) == pytest.approx(1.0, abs=1e-5)

    @given(d=st.integers(4, 16), k1=st.integers(2, 16), k2=st.integers(2, 16),
           seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_symmetry_and_range(self, d, k1, k2, seed):
        a = _rand((d, k1), seed, scale=2.0)
        b = _rand((d, k2), seed + 1, scale=2.0)
        s1, s2 = float(mmcs_sym(a, b)), float(mmcs_sym(b, a))
        assert s1 == pytest.approx(s2, abs=1e-6)
        assert 0.0 <= s1 <= 1.0 + 1e-6
        assert 0.0 <= float(mmcs(a, b)) <= 1.0 + 1e-6

    def test_table(self):
        dicts = {"a": _rand((8, 6), 0), "b": _rand((8, 5), 1),
                 "c": _rand((8, 6), 2)}
        t = mmcs_table(dicts)
        assert set(t) == {("a", "b"), ("a", "c"), ("b", "c")}
        assert t[("a", "b")] == pytest.approx(
            float(mmcs_sym(dicts["a"], dicts["b"])), abs=1e-6)
        assert all(0.0 <= v <= 1.0 + 1e-6 for v in t.values())


# ----------------------------------------------- §7.3 tables tiny regression
# (accuracy %, column sparsity %) at seed=0, 160×96, 25 epochs, re-pinned on
# JAX/jaxlib 0.9.0 (CPU): the numbers first pinned here came from an older
# JAX whose random streams and CPU kernels trained the tiny SAEs to a
# different optimum (baseline 84.4% there, 75.0% here — one 32-sample test
# set, so 3.125% steps). Tolerances cover BLAS-level drift; the structure
# assertions catch method regressions outright
_PINNED = {
    "baseline":      (75.0, 0.0),
    "exact_l1inf":   (75.0, 1.0),
    "bilevel_l1inf": (62.5, 58.3),
    "bilevel_l11":   (75.0, 4.2),
    "bilevel_l12":   (75.0, 8.3),
}


@pytest.mark.slow
def test_sae_tables_tiny_regression_slow():
    # the nightly (-m "") run covers the committed bench config itself
    from repro.training.sae_tables import tables
    rows = tables(full=False)
    assert len(rows) == 10


def test_sae_tables_tiny_regression():
    from repro.training.sae_tables import run_dataset
    from repro.data import classification_synthetic

    x, y, _ = classification_synthetic(n_samples=160, n_features=96,
                                       n_informative=32, class_sep=0.8)
    rows = run_dataset("tiny", x, y, radius=1.0, epochs=25, seed=0)
    got = {}
    for name, _, derived in rows:
        m = re.match(r"sae_tiny_(\w+)", name)
        acc, sp = re.match(r"acc=([\d.]+)%_colsparsity=([\d.]+)%",
                           derived).groups()
        got[m.group(1)] = (float(acc), float(sp))
    assert set(got) == set(_PINNED)
    for method, (acc, sp) in _PINNED.items():
        gacc, gsp = got[method]
        assert gacc == pytest.approx(acc, abs=6.5), method
        assert gsp == pytest.approx(sp, abs=10.0), method
    # structure: only the projected methods sparsify; bi-level ℓ1,∞ dominates
    assert got["baseline"][1] == 0.0
    assert got["bilevel_l1inf"][1] > 40.0
    assert max(got, key=lambda k: got[k][1]) == "bilevel_l1inf"


def test_double_descent_no_rewind_ablation():
    from repro.training.sae_tables import run_dataset
    from repro.data import classification_synthetic

    x, y, _ = classification_synthetic(n_samples=120, n_features=64,
                                       n_informative=16, class_sep=0.8)
    rows = run_dataset("nr", x, y, radius=1.0, epochs=10, seed=0,
                       rewind=False, only=("bilevel_l1inf",))
    assert len(rows) == 1
    sp = float(re.search(r"colsparsity=([\d.]+)%", rows[0][2]).group(1))
    assert sp > 10.0   # the mask (not the rewind) carries the sparsity


# ------------------------------------------------------- end-to-end factory
def test_factory_end_to_end(harvest_dir):
    d, meta = harvest_dir
    run = F.train_sae(d, 0, FCFG, seed=0)
    dm = meta["d_model"]
    assert run["dictionary"].shape == (dm, FCFG.expansion * dm)
    assert np.isfinite(run["metrics"]["loss"])
    # the per-step constraint holds on the FINAL params (projection is the
    # last thing the fused epilogue does)
    rep = F.constraint_report(run["params"], F.sae_projection_spec(FCFG))
    assert rep["feasible"], rep
    # cross-seed MMCS is a proper similarity
    run2 = F.train_sae(d, 0, FCFG, seed=1)
    s = float(mmcs_sym(run["dictionary"], run2["dictionary"]))
    assert 0.0 < s <= 1.0
    # determinism: same seed, same dictionary
    again = F.train_sae(d, 0, FCFG, seed=0)
    np.testing.assert_allclose(run["dictionary"], again["dictionary"],
                               atol=1e-6)


def test_factory_head_structured_tri_level(harvest_dir):
    # heads>1: 3-D encoder (d_in, heads, d//heads), tri-level l1,inf,inf ball
    import dataclasses
    d, meta = harvest_dir
    hcfg = dataclasses.replace(FCFG, heads=2)
    assert F.effective_levels(hcfg) == (("inf", 1),) + tuple(FCFG.levels)
    assert F.sae_projection_spec(hcfg).levels == F.effective_levels(hcfg)
    # an explicit 3-axis design wins over the implicit upgrade
    explicit = dataclasses.replace(
        hcfg, levels=(("2", 1), ("inf", 1), ("1", 1)))
    assert F.effective_levels(explicit) == explicit.levels
    run = F.train_sae(d, 0, hcfg, seed=0)
    dm = meta["d_model"]
    assert run["params"]["enc"]["w"].shape == (dm, 2, hcfg.expansion * dm // 2)
    # the dictionary flattens the head axes back for MMCS
    assert run["dictionary"].shape == (dm, hcfg.expansion * dm)
    rep = F.constraint_report(run["params"], F.sae_projection_spec(hcfg))
    assert rep["feasible"], rep
    assert np.isfinite(run["metrics"]["mse"])


def test_dict_template_head_validation():
    from repro.models import sae
    with pytest.raises(ValueError, match="divisible"):
        sae.dict_template(8, 30, heads=4)
    tpl = sae.dict_template(8, 32, heads=4)
    assert tpl["enc"]["w"].shape == (8, 4, 8)
    assert tpl["dec"]["w"].shape == (4, 8, 8)


def test_head_structured_forward_matches_flat_math():
    # flattening the head axes reproduces the 2-D matmul exactly
    import jax
    from repro.models import params as PM, sae
    key = jax.random.PRNGKey(0)
    p3 = PM.init_params(sae.dict_template(8, 16, heads=4), key)
    p2 = jax.tree_util.tree_map(np.asarray, p3)
    p2["enc"]["w"] = p2["enc"]["w"].reshape(8, 16)
    p2["dec"]["w"] = p2["dec"]["w"].reshape(16, 8)
    x = _rand((6, 8), seed=5)
    f3, r3 = sae.dict_forward(p3, x)
    f2, r2 = sae.dict_forward({k: {kk: jnp.asarray(v) for kk, v in d.items()}
                               for k, d in p2.items()}, x)
    np.testing.assert_allclose(np.asarray(f3), np.asarray(f2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(r3), np.asarray(r2), atol=1e-6)


def test_run_factory_accepts_checkpoint_params(tmp_path):
    # --checkpoint path: harvest from explicit LM weights, not the seeded init
    import dataclasses
    import jax
    fcfg = dataclasses.replace(FCFG, layers=(0,), harvest_steps=1,
                               train_steps=2)
    _, _, params = F.lm_for(fcfg)
    scaled = jax.tree_util.tree_map(lambda w: w * 1.5, params)
    out = F.run_factory(fcfg, tmp_path, seeds=(0,), lm_params=scaled)
    assert 0 in out["layers"]
    # different weights -> different activations than the default harvest
    d2 = tmp_path / "default"
    d2.mkdir()
    F.harvest_activations(fcfg, d2)
    a = np.load(next(tmp_path.glob("layer*_shard*.npy")))
    b = np.load(next(d2.glob("layer*_shard*.npy")))
    assert float(np.abs(a - b).max()) > 1e-6


def test_gsp_whole_network_single_device():
    g = F.gsp_whole_network(steps=1)
    assert g["n_projected"] >= 10       # every ≥2-D weight of the smoke LM
    assert g["feasible"], g
    assert np.isfinite(g["loss"])


_GSP_CHILD = """
import json, jax
from repro.launch.mesh import make_host_mesh
from repro.training import sae_factory as F
assert jax.device_count() == 8, jax.device_count()
g = F.gsp_whole_network(mesh=make_host_mesh(1, 8), steps=2)
print("RESULT" + json.dumps({k: v for k, v in g.items()
                             if k != "per_leaf_sparsity"}))
"""


def test_gsp_whole_network_8dev_mesh_executor():
    """Whole-network GSP through the §3 mesh executor on a forced 8-device
    CPU mesh (subprocess: device count is fixed at interpreter start)."""
    import os
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    res = subprocess.run([sys.executable, "-c", _GSP_CHILD],
                         capture_output=True, text=True, timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    g = json.loads(res.stdout.split("RESULT", 1)[1])
    assert g["n_devices"] == 8
    assert g["n_projected"] >= 10
    assert g["feasible"], g
    # sharded and single-device paths optimize the same function
    ref = F.gsp_whole_network(steps=2)
    assert g["loss"] == pytest.approx(ref["loss"], rel=1e-3)

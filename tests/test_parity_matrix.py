"""The backend x precision x adapt parity matrix.

ONE parametrized surface replaces the ad-hoc per-file backend-parity
tests that used to live in ``test_kernels_batch.py`` / ``test_fleet.py``:

* **backend parity** — for every (precision, adapt) cell, the ``pallas``
  kernel path and the ``jnp`` path produce the same stream outputs
  (scores allclose, gate decisions identical) — across all four
  datapaths (float32 / int8 / packed int4 / binary);
* **precision ranking parity** — for every (backend, adapt, int
  precision) cell, the integer datapath's frame scores *rank*
  identically to the float path's at the matching ADC depth wherever
  the float scores are separated by more than the quantization margin
  (and the absolute perturbation stays under half that margin — which
  makes the ranking assertion a real constraint, not a tautology).
  ``binary`` is deliberately absent here: sign-quantizing both slabs
  and class HVs perturbs scores by ~2x the span at this D (measured),
  so binary holds only the weaker backend/fleet/decision parities and
  its accuracy story lives in the benchmark's D-vs-AUC curve;
* **fleet parity** — for every (backend, precision) cell, ``FleetRunner``
  equals S independent ``StreamRunner``s stream-for-stream;
* **mesh parity** — for every (mesh shape, precision, adapt scope) cell,
  the 2-D (sensors x hyperdim) ``shard_map``'d fleet produces scores,
  gate decisions, AND adapted classifiers bitwise-identical to the
  unsharded runner. Shapes whose device product exceeds the host run
  only under the CI multi-device job (``XLA_FLAGS=--xla_force_host_
  platform_device_count=8``); ``FLEET_TEST_MESH=4x2`` filters the matrix
  to one shape so CI can fan the shapes out across jobs.

Every cell shares ONE module-cached scenario (a gate trained on the
synthetic distribution, so scores are well spread), keeping the matrix
cheap: each runner executes once and every assertion reads the cache.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fragment_model as fm, hypersense
from repro.core.online import AdaptConfig
from repro.core.sensor_control import ControllerConfig
from repro.launch.mesh import make_mesh
from repro.sensing import fragments, synthetic
from repro.sensing.fleet import FleetRunner
from repro.sensing.stream import StreamRunner

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ["jnp", "pallas"]
PRECISIONS = ["float32", "int8", "int4", "binary"]
#: integer precisions that hold the strict ranking-parity contract
#: against the float path at the matching ADC depth (binary does not —
#: see the module docstring)
RANKED_PRECISIONS = ["int8", "int4"]
ADAPTS = [None, "label"]

FRAME, FRAG, STRIDE, DIM = 24, 6, 3, 128
N_STREAM, S_FLEET, N_FLEET = 21, 2, 10
BITS = 8
#: ADC depth each precision runs at (int4 packs two codes per byte, so
#: it is capped at 4 bits; binary sign-quantizes 8-bit-code projections)
PREC_BITS = {"float32": BITS, "int8": BITS, "int4": 4, "binary": BITS}
#: float-score separation below which integer ranking flips are
#: tolerated, as a fraction of the scenario's score span; the matrix
#: also asserts the integer perturbation is < margin / 2, so order on
#: separated pairs is a guaranteed-yet-nontrivial invariant
MARGIN_FRAC = 0.25

_CACHE = {}


def _scenario():
    if _CACHE:
        return _CACHE
    cfg = synthetic.RadarConfig(height=FRAME, width=FRAME)
    frames, masks, labels = synthetic.make_dataset(
        jax.random.PRNGKey(0), 40, cfg)
    frs, labs = fragments.sample_fragments(
        np.asarray(frames), np.asarray(masks), h=FRAG, w=FRAG,
        per_frame=2, seed=0)
    fmodel, _ = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frs), jnp.asarray(labs),
        dim=DIM, epochs=6)
    B0 = fmodel.B.reshape(FRAG, FRAG, -1)[:, 0, :]
    # t_score sits between the positive/negative score bands (asserted in
    # test_scenario_gate_is_nondegenerate), so gate parity is meaningful
    model = hypersense.from_fragment_model(fmodel, B0, h=FRAG, w=FRAG,
                                           stride=STRIDE, t_score=0.0125,
                                           t_detection=1)
    s_frames, _, s_labels = synthetic.make_dataset(
        jax.random.PRNGKey(2), N_STREAM, cfg)
    f_sets = [synthetic.make_dataset(jax.random.PRNGKey(3 + s), N_FLEET,
                                     cfg) for s in range(S_FLEET)]
    f_frames = jnp.stack([fs[0] for fs in f_sets])
    f_labels = np.stack([np.asarray(fs[2]) for fs in f_sets])
    _CACHE.update(model=model, frames=s_frames,
                  labels=np.asarray(s_labels), fleet=f_frames,
                  fleet_labels=f_labels, runs={})
    return _CACHE


def _run_stream(backend, precision, adapt, bits=None):
    sc = _scenario()
    bits = PREC_BITS[precision] if bits is None else bits
    k = ("stream", backend, precision, adapt, bits)
    if k not in sc["runs"]:
        a = (AdaptConfig(mode="label", lr=0.5) if adapt == "label"
             else None)
        r = StreamRunner(sc["model"], ControllerConfig(hold_frames=2),
                         chunk_size=8, backend=backend, block_d=64,
                         adc_bits=bits, precision=precision, adapt=a)
        feed = sc["labels"] if adapt == "label" else None
        sc["runs"][k] = r.process(sc["frames"], labels=feed)
    return sc["runs"][k]


def _run_fleet(backend, precision):
    sc = _scenario()
    k = ("fleet", backend, precision)
    if k not in sc["runs"]:
        r = FleetRunner(sc["model"], ControllerConfig(hold_frames=2),
                        chunk_size=4, backend=backend, block_d=64,
                        adc_bits=PREC_BITS[precision], precision=precision)
        sc["runs"][k] = r.process(sc["fleet"])
    return sc["runs"][k]


def _run_fleet_singles(backend, precision):
    sc = _scenario()
    k = ("fleet-singles", backend, precision)
    if k not in sc["runs"]:
        outs = []
        for s in range(S_FLEET):
            r = StreamRunner(sc["model"], ControllerConfig(hold_frames=2),
                             chunk_size=4, backend=backend, block_d=64,
                             adc_bits=PREC_BITS[precision],
                             precision=precision)
            outs.append(r.process(sc["fleet"][s]))
        sc["runs"][k] = outs
    return sc["runs"][k]


# ---------------------------------------------------------------------------
# backend parity: pallas == jnp in every (precision, adapt) cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("adapt", ADAPTS)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_backend_parity(precision, adapt):
    s_j, f_j, g_j = _run_stream("jnp", precision, adapt)
    s_p, f_p, g_p = _run_stream("pallas", precision, adapt)
    np.testing.assert_allclose(s_p, s_j, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(f_p, f_j)
    np.testing.assert_array_equal(g_p, g_j)


# ---------------------------------------------------------------------------
# precision parity: int8/int4 rank like float32 in every (backend, adapt)
# cell, at the matching ADC depth
# ---------------------------------------------------------------------------

def test_scenario_gate_is_nondegenerate():
    """The shared scenario must exercise both gate outcomes — otherwise
    the matrix's fired/gated equalities would be vacuous."""
    _, fired, _ = _run_stream("jnp", "float32", None)
    assert fired.any() and not fired.all()


@pytest.mark.parametrize("iprec", RANKED_PRECISIONS)
@pytest.mark.parametrize("adapt", ADAPTS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_precision_ranking_parity(backend, adapt, iprec):
    """The float comparator runs at the SAME ADC depth as the integer
    path (float32@4bits for int4) — so the margin bounds quantization
    *of the datapath*, not of the converter."""
    bits = PREC_BITS[iprec]
    s_f, _, _ = _run_stream(backend, "float32", adapt, bits=bits)
    s_i, _, _ = _run_stream(backend, iprec, adapt)
    margin = MARGIN_FRAC * float(s_f.max() - s_f.min())
    # absolute perturbation stays under half the separation margin...
    assert np.abs(s_i - s_f).max() < margin / 2
    # ...so separated pairs must rank identically — and the scenario has
    # to actually contain separated pairs for this to mean anything
    df = s_f[:, None] - s_f[None, :]
    di = s_i[:, None] - s_i[None, :]
    sep = np.abs(df) > margin
    assert sep.sum() > 0.3 * sep.size, "scenario lost its score spread"
    assert (np.sign(di[sep]) == np.sign(df[sep])).all()


@pytest.mark.parametrize("iprec", ["int8", "int4", "binary"])
def test_precision_scores_not_identical(iprec):
    """Each integer precision really is a different datapath (guards
    against the precision flag silently routing to the float kernel, or
    int4/binary silently routing to int8)."""
    s_f, _, _ = _run_stream("pallas", "float32", None)
    s_i, _, _ = _run_stream("pallas", iprec, None)
    assert np.abs(s_i - s_f).max() > 0.0
    if iprec != "int8":
        s_8, _, _ = _run_stream("pallas", "int8", None)
        assert np.abs(s_i - s_8).max() > 0.0


def test_stream_runner_deterministic_per_precision():
    """Two fresh runners over the same frames produce bitwise-identical
    scores for every precision — the deterministic-accumulation-order
    contract at the runner level (the kernel-level twin lives in
    test_int_datapath.py)."""
    sc = _scenario()
    for precision in PRECISIONS:
        runs = []
        for _ in range(2):
            r = StreamRunner(sc["model"], ControllerConfig(hold_frames=2),
                             chunk_size=8, backend="pallas", block_d=64,
                             adc_bits=PREC_BITS[precision],
                             precision=precision)
            runs.append(r.process(sc["frames"]))
        np.testing.assert_array_equal(runs[0][0], runs[1][0])
        np.testing.assert_array_equal(runs[0][2], runs[1][2])


# ---------------------------------------------------------------------------
# fleet parity: FleetRunner == S independent StreamRunners per cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fleet_matches_independent_runners(backend, precision):
    s_f, f_f, g_f = _run_fleet(backend, precision)
    singles = _run_fleet_singles(backend, precision)
    for s, (s_i, f_i, g_i) in enumerate(singles):
        np.testing.assert_allclose(s_f[s], s_i, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(f_f[s], f_i)
        np.testing.assert_array_equal(g_f[s], g_i)


def test_fleet_pallas_bitwise_matches_stream_runner():
    """The kernel grid's batch axis is parallel: flattening S*C changes
    nothing at all (stronger than allclose) — on both precisions."""
    for precision in PRECISIONS:
        s_f, _, _ = _run_fleet("pallas", precision)
        singles = _run_fleet_singles("pallas", precision)
        for s, (s_i, _, _) in enumerate(singles):
            np.testing.assert_array_equal(s_f[s], s_i)


# ---------------------------------------------------------------------------
# mesh parity: every (mesh shape, precision, adapt scope) cell of the 2-D
# (sensors x hyperdim) sharded fleet is BITWISE-identical to unsharded
# ---------------------------------------------------------------------------

#: (data, model) mesh shapes of the acceptance matrix. The fleet's S=2
#: pads up to the data extent (masked slots), and the hyperdim rule
#: claims "model" for the n_dt = DIM / MESH_BLOCK_D = 8 tile axis — so
#: 4x2/2x4/1x8 really partition D across devices.
MESH_SHAPES = {"1x1": (1, 1), "8x1": (8, 1), "4x2": (4, 2),
               "2x4": (2, 4), "1x8": (1, 8)}
#: block_d for the mesh cells: n_dt = 128/16 = 8 divides every model-axis
#: extent in MESH_SHAPES, so the hyperdim axis shards in every shape
MESH_BLOCK_D = 16
#: backend per precision: pallas pins the kernel path (float + the packed
#: int kernel); jnp pins the tiled oracle the int precisions serve from
#: on CPU fleets. int8-pallas-sharded is covered by tests/test_fleet.py
#: and the golden fixture.
MESH_BACKEND = {"float32": "pallas", "int8": "jnp", "int4": "pallas",
                "binary": "jnp"}
SCOPES = ["shared", "per-stream"]


def _mesh_or_skip(name: str):
    want = os.environ.get("FLEET_TEST_MESH")
    if want and name != want:
        pytest.skip(f"FLEET_TEST_MESH={want} filters out {name}")
    shape = MESH_SHAPES[name]
    if shape[0] * shape[1] > jax.device_count():
        pytest.skip(f"mesh {name} needs {shape[0] * shape[1]} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return make_mesh(shape, ("data", "model"))


def _run_fleet_mesh(precision, scope, mesh_name=None):
    sc = _scenario()
    k = ("fleet-mesh", precision, scope, mesh_name)
    if k not in sc["runs"]:
        def go():
            r = FleetRunner(sc["model"], ControllerConfig(hold_frames=2),
                            chunk_size=4, backend=MESH_BACKEND[precision],
                            block_d=MESH_BLOCK_D,
                            adc_bits=PREC_BITS[precision],
                            precision=precision,
                            adapt=AdaptConfig(mode="label", lr=0.5,
                                              scope=scope))
            s, f, g = r.process(sc["fleet"], labels=sc["fleet_labels"])
            return s, f, g, np.asarray(r.class_hvs)

        if mesh_name is None:
            sc["runs"][k] = go()
        else:
            from repro.distributed import sharding as shlib
            with shlib.use_mesh(_mesh_or_skip(mesh_name)):
                sc["runs"][k] = go()
    return sc["runs"][k]


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("mesh_name", list(MESH_SHAPES))
def test_mesh_matrix_bitwise(mesh_name, precision, scope):
    """Sharded scores, gate decisions, and adapted class_hvs are
    bitwise-identical to the unsharded runner in every cell — the
    ordered tile fold + all_gathered shared-scope fold guarantee, not an
    allclose."""
    got = _run_fleet_mesh(precision, scope, mesh_name)   # skips w/o mesh
    want = _run_fleet_mesh(precision, scope, None)
    for name, a, b in zip(("scores", "fired", "gated", "class_hvs"),
                          want, got):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_mesh_matrix_adapts_nontrivially():
    """The mesh cells' classifiers actually moved — so the class_hvs
    equality above compares real adapted state, not the initial model."""
    sc = _scenario()
    for scope in SCOPES:
        chvs = _run_fleet_mesh("float32", scope, None)[3]
        assert not np.allclose(chvs, np.asarray(sc["model"].class_hvs))

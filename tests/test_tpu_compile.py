"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each program is lowered and compiled for one chip of a
v5e:2x2 topology that is described, not attached, so what Mosaic or the
TPU compiler would refuse (tile alignment, VMEM, device memory) fails
here at no chip time. Shapes are the mixed5k bench config's node and
wave widths: N=8192 node slots, P=256 pods per wave.

The topology is described inside a module fixture, never at import:
only one process may load libtpu at a time, and every xdist worker
imports every test file. All TPU compiles live in this one file, so
one worker loads the library.
"""

import numpy as np
import pytest

N, P = 8192, 256


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def world():
    """Host planes of a Snapshot pre-sized to N node slots and one
    P-pod featurized wave (numpy only; nothing touches a device)."""
    from kubernetes_tpu.ops import encoding as enc
    from kubernetes_tpu.state.featurize import PodFeaturizer
    from kubernetes_tpu.state.snapshot import Snapshot

    from helpers import make_node, make_pod

    snap = Snapshot(caps=enc.Caps(N=N, P=P))
    from kubernetes_tpu.state.cache import SchedulerCache

    cache = SchedulerCache()
    for i in range(4):
        n = make_node(f"n{i}")
        cache.add_node(n)
        snap.set_node(cache.node_infos[n.name])
    pb = PodFeaturizer(snap).featurize([make_pod(f"p{i}") for i in range(P)])
    assert pb.req.shape[0] == P and snap.caps.N == N
    return snap, pb


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _compile(jitted, args, kwargs):
    return jitted.lower(*args, **kwargs).compile().as_text()


def test_taint_ports_kernel_compiles(one_chip, world):
    from kubernetes_tpu.ops.pallas_kernels import taint_ports_masks

    snap, pb = world
    nt = snap.node_tensors()
    text = _compile(taint_ports_masks,
                    _shapes((nt, pb), one_chip), {})
    assert "tpu_custom_call" in text


def test_wave_program_compiles_with_pallas(one_chip, world):
    from kubernetes_tpu.ops.kernel import Weights, _schedule_wave

    snap, pb = world
    nt, pm, tt = snap.host_tensors()
    extra = np.ones((P, N), bool)
    rr = np.int32(0)
    args = _shapes((nt, pm, tt, pb, extra, rr), one_chip)
    text = _compile(_schedule_wave, args, dict(
        weights=Weights(), num_zones=snap.caps.Z,
        num_label_values=snap.num_label_values, has_ipa=True,
        use_pallas=True))
    assert "tpu_custom_call" in text


def test_round_program_compiles_with_pallas(one_chip, world):
    """The device-resident round at its smallest wave-count bucket, with
    the inter-pod affinity plane compiled in — the program every
    straggler round of an affinity workload runs."""
    from kubernetes_tpu.ops import encoding as enc
    from kubernetes_tpu.ops.kernel import Weights, _schedule_round
    from kubernetes_tpu.ops.scores import stack_weights
    from kubernetes_tpu.sched.scheduler import pipeline_bucket

    snap, pb = world
    W = pipeline_bucket(1)
    nt, pm, tt = snap.host_tensors()
    pbs = enc.PodBatch(*[np.stack([a] * W) for a in pb])
    usage = (nt.requested, nt.nonzero, nt.pod_count)
    rows = np.full((W, P), -1, np.int32)
    trows = np.full((W, P, 2), -1, np.int32)
    args = _shapes((nt, pm, tt, pbs, usage, np.int32(0), rows, trows),
                   one_chip)
    text = _compile(_schedule_round, args, dict(
        weights=Weights(), num_zones=snap.caps.Z,
        num_label_values=snap.num_label_values, has_ipa=True,
        use_pallas=True,
        weight_vec=_shapes(stack_weights(Weights()), one_chip)))
    assert "tpu_custom_call" in text

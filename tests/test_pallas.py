"""Pallas filter-kernel parity: the fused taint+port kernel (interpret
mode on CPU) must agree exactly with the XLA broadcast formulation in
ops/filters.py on randomized worlds — the same golden-parity discipline
the tensor kernels get against plugins/golden.py."""

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.featurize import PodFeaturizer
from kubernetes_tpu.state.snapshot import Snapshot


def build_world(rng, n_nodes=24, n_pods=12):
    from kubernetes_tpu.api.labels import Selector
    cache, snap = SchedulerCache(), Snapshot()
    effects = [api.NO_SCHEDULE, api.PREFER_NO_SCHEDULE, api.NO_EXECUTE]
    for i in range(n_nodes):
        taints = []
        for t in range(rng.integers(0, 3)):
            taints.append(api.Taint(key=f"k{rng.integers(0, 4)}",
                                    value=f"v{rng.integers(0, 3)}",
                                    effect=effects[rng.integers(0, 3)]))
        node = api.Node(
            metadata=api.ObjectMeta(name=f"n{i}"),
            spec=api.NodeSpec(taints=taints),
            status=api.NodeStatus(
                allocatable=api.resource_list(cpu="8", memory="16Gi",
                                              pods=110),
                conditions=[api.NodeCondition(api.NODE_READY,
                                              api.COND_TRUE)]))
        cache.add_node(node)
        snap.set_node(cache.node_infos[node.name])
    # existing pods with host ports occupy node port slots
    for i in range(n_pods // 2):
        port = int(rng.integers(8000, 8004))
        p = api.Pod(
            metadata=api.ObjectMeta(name=f"e{i}"),
            spec=api.PodSpec(
                node_name=f"n{int(rng.integers(0, n_nodes))}",
                containers=[api.Container(ports=[api.ContainerPort(
                    container_port=port, host_port=port)])]))
        cache.add_pod(p)
        snap.refresh_node_resources(cache.node_infos[p.spec.node_name])
        snap.add_pod(p)
    feat = PodFeaturizer(snap, group_selectors=lambda p: [])
    pods = []
    ops = [api.TOLERATION_OP_EQUAL, api.TOLERATION_OP_EXISTS]
    for i in range(n_pods):
        tols = []
        for t in range(rng.integers(0, 3)):
            tols.append(api.Toleration(
                key=f"k{rng.integers(0, 4)}" if rng.random() > 0.2 else "",
                operator=ops[rng.integers(0, 2)],
                value=f"v{rng.integers(0, 3)}",
                effect=effects[rng.integers(0, 3)] if rng.random() > 0.3 else ""))
        ports = []
        if rng.random() > 0.5:
            port = int(rng.integers(8000, 8004))
            ports = [api.ContainerPort(container_port=port, host_port=port)]
        pods.append(api.Pod(
            metadata=api.ObjectMeta(name=f"p{i}"),
            spec=api.PodSpec(tolerations=tols,
                             containers=[api.Container(ports=ports)])))
    return snap, feat.featurize(pods)


class TestPallasParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_taint_ports_parity(self, seed):
        from kubernetes_tpu.ops import encoding as enc
        from kubernetes_tpu.ops.filters import host_ports, tolerates_taints
        from kubernetes_tpu.ops.pallas_kernels import taint_ports_masks
        rng = np.random.default_rng(seed)
        snap, pb = build_world(rng)
        nt, _, _ = snap.to_device()
        want_taints = np.asarray(tolerates_taints(
            nt, pb, (enc.EFFECT_NO_SCHEDULE, enc.EFFECT_NO_EXECUTE)))
        want_ports = np.asarray(host_ports(nt, pb))
        got_taints, got_ports = taint_ports_masks(nt, pb, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_taints), want_taints)
        np.testing.assert_array_equal(np.asarray(got_ports), want_ports)

    def test_wave_with_pallas_matches(self):
        """Full schedule_wave with the pallas filter path (interpret) ==
        stock wave on the same world."""
        from kubernetes_tpu.ops.kernel import Weights, schedule_wave
        import jax.numpy as jnp
        rng = np.random.default_rng(7)
        snap, pb = build_world(rng, n_nodes=16, n_pods=8)
        nt, pm, tt = snap.to_device()
        extra = np.ones((pb.req.shape[0], snap.caps.N), bool)
        rr = jnp.asarray(0, jnp.int32)
        kw = dict(weights=Weights(), num_zones=snap.caps.Z,
                  num_label_values=snap.num_label_values, has_ipa=False)
        base = schedule_wave(nt, pm, tt, pb, extra, rr, **kw)
        pal = schedule_wave(nt, pm, tt, pb, extra, rr, use_pallas=True,
                            pallas_interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(base.chosen),
                                      np.asarray(pal.chosen))
        np.testing.assert_array_equal(np.asarray(base.masks),
                                      np.asarray(pal.masks))

    def test_round_with_hoisted_pallas_matches(self):
        """schedule_round with use_pallas (the hoisted pre-scan Pallas
        pass, interpret mode) == stock round on a taint/port-rich world:
        placements AND fail counts, across multiple chained waves."""
        import jax.numpy as jnp
        from kubernetes_tpu.ops.kernel import Weights, schedule_round
        from kubernetes_tpu.sched.scheduler import assemble_round

        rng = np.random.default_rng(11)
        snap, _ = build_world(rng, n_nodes=16, n_pods=0)
        feat = PodFeaturizer(snap, group_selectors=lambda p: [])
        pods_all = []
        effects = [api.NO_SCHEDULE, api.NO_EXECUTE]
        for i in range(18):
            tols = ([api.Toleration(key=f"k{i % 4}",
                                    operator=api.TOLERATION_OP_EXISTS,
                                    effect=effects[i % 2])]
                    if i % 3 else [])
            port = [api.ContainerPort(container_port=8000 + i % 4,
                                      host_port=8000 + i % 4)] \
                if i % 2 else []
            pods_all.append(api.Pod(
                metadata=api.ObjectMeta(name=f"w{i}"),
                spec=api.PodSpec(
                    tolerations=tols,
                    containers=[api.Container(
                        ports=port,
                        resources=api.ResourceRequirements(
                            requests=api.resource_list(cpu="100m")))])))
        W = 6
        waves = [pods_all[i:i + W] for i in range(0, len(pods_all), W)]
        # featurize twice: pass 1 grows the toleration/port vocabs, pass
        # 2 re-emits every wave at the final (uniform) shapes
        [feat.featurize(wv) for wv in waves]
        pbs = [feat.featurize(wv) for wv in waves]
        pm_rows, term_rows = snap.stage_pending(pods_all)
        nt, pm, tt = snap.to_device()
        usage = (nt.requested, nt.nonzero, nt.pod_count)
        pbs_stacked, rows, trows = assemble_round(
            pbs, waves, pm_rows, term_rows, 4, term_rows.shape[1])
        kw = dict(weights=Weights(), num_zones=snap.caps.Z,
                  num_label_values=snap.num_label_values, has_ipa=False)
        base = schedule_round(nt, pm, tt, pbs_stacked, usage,
                              jnp.asarray(0, jnp.int32), rows, trows, **kw)
        pal = schedule_round(nt, pm, tt, pbs_stacked, usage,
                             jnp.asarray(0, jnp.int32), rows, trows,
                             use_pallas=True, pallas_interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(base[0]),
                                      np.asarray(pal[0]))  # chosen
        np.testing.assert_array_equal(np.asarray(base[1]),
                                      np.asarray(pal[1]))  # fail_counts
        # sanity: the world actually exercises the kernels (some pod
        # failed or some taint exists)
        assert int(np.asarray(nt.taint_key).max()) > 0

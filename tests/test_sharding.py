"""Sharding-rule tests (pure logic on an AbstractMesh — no devices), plus
the mesh-scale determinism contract: committed streams bitwise-identical
across logical TP widths and replica counts, the pinned canonical tree
realized identically on real shard_map meshes (subprocess, faked host
devices), and the un-pinned fast path as negative control."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, get_smoke_config, list_archs
from repro.core.determinism import Mode, Schedule, matmul
from repro.distributed import sharding
from repro.launch.specs import INPUT_SHAPES, resolve_config
from repro.models import init_params
from repro.serving.engine import Engine
from repro.serving.request import Request, SamplingParams
from repro.serving.scheduler import (
    AdaptivePolicy,
    OverlapPolicy,
    PauseDecodePolicy,
)


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


class TestSpecFor:
    def test_basic_tp(self):
        m = _mesh()
        s = sharding.spec_for((4096, 14336), ("embed", "ffn"),
                              sharding.rules_serve(m), m)
        assert s == P(None, "model")

    def test_fsdp_train(self):
        m = _mesh()
        s = sharding.spec_for((4096, 14336), ("embed", "ffn"),
                              sharding.rules_train(m), m)
        assert s == P("data", "model")

    def test_divisibility_fallback(self):
        m = _mesh()
        # kv dim 1024 divides 16; 8 does not -> dropped to replicated
        s = sharding.spec_for((8,), ("kv",), sharding.rules_serve(m), m)
        assert s == P(None)

    def test_no_axis_reuse(self):
        m = _mesh()
        # experts->model and ffn->model would reuse 'model'; first dim wins
        s = sharding.spec_for((16, 4096, 8192), ("experts", "embed", "ffn"),
                              sharding.rules_serve(m), m)
        assert s == P("model", None, None)

    def test_multipod_fsdp_uses_both_data_axes(self):
        m = _mesh(multi=True)
        s = sharding.spec_for((8192, 1024), ("embed", "ffn"),
                              sharding.rules_train(m), m)
        assert s == P(("pod", "data"), "model")

    def test_multipod_nondivisible_drops_right(self):
        m = _mesh(multi=True)
        # 16 % (2*16) != 0 -> drop 'data' from the right, keep 'pod'? No:
        # the rule drops right-to-left until divisible: ('pod','data')->('pod',)
        s = sharding.spec_for((16,), ("embed",), sharding.rules_train(m), m)
        assert s == P("pod")


class TestParamPspecs:
    @pytest.mark.parametrize("arch", list_archs())
    def test_every_param_gets_a_valid_spec(self, arch):
        cfg = get_config(arch)
        m = _mesh(multi=True)
        specs = sharding.param_pspecs(cfg, m, sharding.rules_train(m))
        sizes = dict(zip(m.axis_names, m.axis_sizes))
        from repro.models.base import param_specs

        for (path, ps), (_, spec) in zip(
            jax.tree_util.tree_leaves_with_path(specs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_leaves_with_path(param_specs(cfg)),
        ):
            used = set()
            for dim, part in zip(spec.shape, tuple(ps) + (None,) * 10):
                if part is None:
                    continue
                axes = (part,) if isinstance(part, str) else part
                prod = int(np.prod([sizes[a] for a in axes]))
                assert dim % prod == 0, (arch, path, spec.shape, ps)
                for a in axes:
                    assert a not in used, (arch, path, ps)
                    used.add(a)


class TestHostMesh:
    def test_non_divisible_model_axis_raises_readable(self):
        from repro.launch.mesh import make_host_mesh

        n = len(jax.devices())
        with pytest.raises(ValueError) as ei:
            make_host_mesh(model=n + 3)  # never divides
        msg = str(ei.value)
        # the message must name the actual device count and the remedy
        assert str(n) in msg
        assert "xla_force_host_platform_device_count" in msg

    def test_zero_model_axis_raises(self):
        from repro.launch.mesh import make_host_mesh

        with pytest.raises(ValueError):
            make_host_mesh(model=0)

    def test_divisible_model_axis_ok(self):
        from repro.launch.mesh import make_host_mesh

        m = make_host_mesh(model=1)
        assert m.axis_names == ("data", "model")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("llama3-8b")
    return cfg, init_params(cfg, jax.random.key(0))


def _det_reqs(cfg, n=3, max_new=8):
    return [
        Request(
            rid=i, prompt=[(5 * i + j) % cfg.vocab_size for j in range(9)],
            sampling=SamplingParams(
                max_new_tokens=max_new, is_deterministic=True, seed=70 + i,
            ),
        )
        for i in range(n)
    ]


_SCHEDULERS = {
    "pause": PauseDecodePolicy,
    "overlap": OverlapPolicy,
    "adaptive": AdaptivePolicy,
}


class TestTPInvariantCommit:
    """The tentpole theorem at engine level: the fast path may run at any
    logical TP width, but commits replay under the canonical mesh schedule,
    so committed streams are bitwise TP-invariant."""

    @pytest.mark.parametrize("scheduler", sorted(_SCHEDULERS))
    def test_committed_streams_bitwise_across_tp(self, smoke_model,
                                                 scheduler):
        cfg, params = smoke_model
        streams = {}
        for tp in (1, 2, 4):
            eng = Engine(cfg, params, mode=Mode.LLM42, window=4, group=2,
                         max_batch=4, capacity=128,
                         scheduler=_SCHEDULERS[scheduler](), tp=tp)
            for r in _det_reqs(cfg):
                eng.submit(r)
            streams[tp] = {
                r.rid: tuple(r.committed) for r in eng.run()
            }
        assert streams[1] == streams[2] == streams[4]

    def test_fast_path_tp_variant_negative_control(self):
        """The un-pinned fast path MUST vary across TP widths — if it did
        not, the pinned commit tree would be vacuous (nothing to defend
        against) and the prover's negative control would be meaningless."""
        x = jax.random.normal(jax.random.key(3), (4, 64), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(4), (64, 32), jnp.bfloat16)
        fast1 = Schedule(splits=2, combine_dtype="bfloat16",
                         tp_shards=1, tp_pinned=False)
        fast4 = Schedule(splits=2, combine_dtype="bfloat16",
                         tp_shards=4, tp_pinned=False)
        assert not bool(jnp.array_equal(matmul(x, w, fast1),
                                        matmul(x, w, fast4)))

    def test_pinned_tree_is_tp_invariant_logically(self):
        """The canonical pinned decomposition is a fixed logical program:
        the same schedule evaluates to the same bits no matter what width
        the caller models (it never reads a mesh)."""
        from repro.core.determinism import VERIFY_SCHEDULE

        x = jax.random.normal(jax.random.key(5), (4, 64), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(6), (64, 32), jnp.bfloat16)
        a = matmul(x, w, VERIFY_SCHEDULE)
        b = matmul(x, w, VERIFY_SCHEDULE._replace())  # fresh equal schedule
        assert bool(jnp.array_equal(a, b))

    def test_tp_matmul_mesh_widths_bitwise(self):
        """Real shard_map execution: the pinned canonical tree commits the
        same bits on host meshes of width 1, 2 and 4, and equals the
        logical (unsharded) canonical matmul; the un-pinned fast schedule
        diverges between widths (negative control).  Runs in a subprocess
        because the faked 8-device host platform must be configured before
        jax initializes."""
        script = textwrap.dedent("""
            import jax, jax.numpy as jnp
            from repro.core.determinism import (
                Schedule, VERIFY_SCHEDULE, matmul)
            from repro.distributed.sharding import tp_matmul
            from repro.launch.mesh import make_host_mesh

            x = jax.random.normal(jax.random.key(0), (4, 64), jnp.bfloat16)
            w = jax.random.normal(jax.random.key(1), (64, 32), jnp.bfloat16)
            ref = matmul(x, w, VERIFY_SCHEDULE)
            for d in (1, 2, 4):
                mesh = make_host_mesh(model=d)
                got = tp_matmul(x, w, mesh, schedule=VERIFY_SCHEDULE)
                assert jnp.array_equal(ref, got), f"width {d} diverged"
            fast = Schedule(splits=1, combine_dtype="bfloat16",
                            tp_shards=4, tp_pinned=False)
            a = tp_matmul(x, w, make_host_mesh(model=1), schedule=fast)
            b = tp_matmul(x, w, make_host_mesh(model=4), schedule=fast)
            assert not jnp.array_equal(a, b), (
                "un-pinned fast path failed to diverge across widths")
            print("ALL-OK")
        """)
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        env["PYTHONPATH"] = (
            os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr
        assert "ALL-OK" in proc.stdout


class TestRouterDeterminism:
    """Cluster layer of the contract: same arrival trace => same
    request->replica assignment => same committed streams, bitwise, at any
    replica count."""

    def _once(self, smoke_model, n_replicas):
        from repro.cluster import Cluster, run_online

        cfg, params = smoke_model

        def make_engine(idx):
            return Engine(cfg, params, mode=Mode.LLM42, window=4, group=2,
                          max_batch=2, capacity=128)

        cluster = Cluster(make_engine, n_replicas)
        reqs = _det_reqs(cfg, n=6)
        arrivals = [0.0] * 6
        res = run_online(cluster, cfg, list(zip(reqs, arrivals)))
        streams = {r.rid: tuple(r.committed) for r in cluster.finished}
        return res.assignment, streams

    def test_streams_bitwise_across_replica_counts(self, smoke_model):
        a1, s1 = self._once(smoke_model, 1)
        a2, s2 = self._once(smoke_model, 2)
        a4, s4 = self._once(smoke_model, 4)
        assert len(s1) == 6
        assert s1 == s2 == s4
        # more replicas actually get used when load warrants it
        assert set(a2.values()) == {0, 1}
        assert set(a4.values()) == {0, 1, 2, 3}

    def test_assignment_is_reproducible(self, smoke_model):
        a, s = self._once(smoke_model, 2)
        b, t = self._once(smoke_model, 2)
        assert a == b
        assert s == t


class TestCacheSpecs:
    @pytest.mark.parametrize("arch", list_archs())
    @pytest.mark.parametrize("shape", list(INPUT_SHAPES))
    def test_cache_specs_divisible(self, arch, shape):
        cfg, skip = resolve_config(arch, shape)
        if skip or INPUT_SHAPES[shape]["kind"] == "train":
            pytest.skip("n/a")
        from repro.launch.specs import decode_capacity
        from repro.models.transformer import cache_spec

        m = _mesh()
        meta = INPUT_SHAPES[shape]
        cap = decode_capacity(cfg, meta["seq"])
        tree = sharding.cache_pspec_tree(cfg, m, meta["batch"], cap)
        spec = cache_spec(cfg, meta["batch"], cap)
        sizes = dict(zip(m.axis_names, m.axis_sizes))
        for ps, s in zip(jax.tree_util.tree_leaves(
                tree, is_leaf=lambda x: isinstance(x, P)),
                jax.tree_util.tree_leaves(spec)):
            for dim, part in zip(s.shape, tuple(ps) + (None,) * 10):
                if part is None:
                    continue
                axes = (part,) if isinstance(part, str) else part
                prod = int(np.prod([sizes[a] for a in axes]))
                assert dim % prod == 0, (arch, shape, s.shape, ps)

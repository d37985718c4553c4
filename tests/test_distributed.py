"""Distributed tests: sharding rules, shard_map collectives on 8 fake devices
(subprocess -- the main test process must keep seeing 1 CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# Forcing N host devices on a machine with far fewer cores makes XLA
# compilation exceed the subprocess budget (observed: >300s on 2 cores), so
# the emulated-mesh tests gate on a minimum core count.
_HOST_CPUS = os.cpu_count() or 1

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingRules


def test_spec_building():
    r = ShardingRules({"batch": ("pod", "data"), "heads": "model",
                       "embed": None})
    assert r.spec("batch", None, "heads") == P(("pod", "data"), None, "model")
    assert r.spec("embed") == P()
    assert r.spec(None, "embed") == P()


def test_spec_no_duplicate_physical_axes():
    r = ShardingRules({"a": ("data", "model"), "b": "model"})
    spec = r.spec("a", "b")
    # 'model' already used by axis a -> b falls back to replicated
    assert spec == P(("data", "model"))


def test_with_overrides_immutable():
    r1 = ShardingRules({"a": "data"})
    r2 = r1.with_overrides(a=None, b="model")
    assert r1.rules["a"] == "data"
    assert r2.rules["a"] is None and r2.rules["b"] == "model"


_SUBPROCESS_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core.vector_index import scan_topk
    from repro.distributed.collectives import partial_softmax_combine, sharded_topk

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    corpus = jnp.asarray(rng.standard_normal((1024, 16)), jnp.float32)
    ids = jnp.arange(1024)
    q = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    with jax.set_mesh(mesh):
        v_d, i_d = sharded_topk(mesh, "data", q, corpus, ids, 8)
    v_g, i_g = scan_topk(q, corpus, ids, 8)
    ok_topk = bool(np.allclose(np.asarray(v_d), np.asarray(v_g), rtol=1e-4))

    scores = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
    values = jnp.asarray(rng.standard_normal((4, 512, 8)), jnp.float32)
    with jax.set_mesh(mesh):
        out_d = partial_softmax_combine(mesh, "data", scores, values)
    p = jax.nn.softmax(scores, axis=-1)
    out_g = jnp.einsum("qs,qsd->qd", p, values)
    ok_soft = bool(np.allclose(np.asarray(out_d), np.asarray(out_g),
                               rtol=1e-4, atol=1e-5))
    print(json.dumps({"topk": ok_topk, "softmax": ok_soft}))
""")


@pytest.mark.slow
@pytest.mark.skipif(_HOST_CPUS < 4,
                    reason="needs >=4 cores to emulate 8 XLA host devices "
                           "within the subprocess time budget")
def test_shardmap_collectives_8dev():
    res = subprocess.run([sys.executable, "-c", _SUBPROCESS_SNIPPET],
                         capture_output=True, text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out == {"topk": True, "softmax": True}


@pytest.mark.slow
@pytest.mark.skipif(_HOST_CPUS < 8,
                    reason="needs >=8 cores to emulate 16 XLA host devices "
                           "within the subprocess time budget")
def test_reduced_model_lowering_on_16dev():
    """A reduced LM lowers + compiles on a 4x4 mesh (mini dry-run)."""
    snippet = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs.base import TransformerConfig
        from repro.distributed.sharding import base_rules, tree_shardings
        from repro.models.transformer import LM

        mesh = jax.make_mesh((4, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = TransformerConfig(n_layers=2, d_model=64, n_heads=8,
                                n_kv_heads=4, head_dim=16, d_ff=256,
                                vocab_size=512, dtype="float32")
        m = LM(cfg)
        rules = base_rules(mesh)
        p_abs = jax.eval_shape(m.init, jax.random.key(0))
        p_sh = tree_shardings(mesh, rules, m.param_axes())
        tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
        def loss(p, t):
            return m.loss_fn(p, t, t, rules)[0]
        with jax.set_mesh(mesh):
            c = jax.jit(loss, in_shardings=(p_sh, None)).lower(p_abs, tok).compile()
        print(json.dumps({"ok": True,
                          "flops": c.cost_analysis().get("flops", 0)}))
    """)
    res = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stderr[-2000:]
    assert json.loads(res.stdout.strip().splitlines()[-1])["ok"]

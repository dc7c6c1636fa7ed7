"""Distribution tests: sharding rules for every arch + a real multi-device
lower/compile, run in a subprocess so the host-device-count flag never leaks
into the other tests' single-device view."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import all_arch_ids

_SUBPROCESS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import all_arch_ids, get_config, SHAPES
    from repro.models import registry
    from repro.parallel import sharding as sh
    from repro.runtime import train_loop

    mesh = make_mesh((4, 2), ("data", "model"))
    out = {"specs_ok": [], "lowered": []}

    # 1) sharding rules produce valid NamedShardings for every FULL config
    for arch in all_arch_ids():
        cfg = get_config(arch)
        tree = registry.param_shapes(cfg)
        for mode in ("train", "serve"):
            specs = sh.param_specs(cfg, tree, mesh, mode)
            def check(leaf, spec):
                s = NamedSharding(mesh, spec)
                s.shard_shape(leaf.shape)  # raises if indivisible
            jax.tree.map(check, tree, specs,
                         is_leaf=lambda x: isinstance(x, P))
        out["specs_ok"].append(arch)

    # 2) real lower+compile of reduced train and decode steps on the mesh
    for arch in ("qwen3-14b", "grok-1-314b", "rwkv6-3b"):
        cfg = get_config(arch, reduced=True)
        tree = registry.param_shapes(cfg)
        pspecs = sh.param_specs(cfg, tree, mesh, "train")
        state = train_loop.train_state_struct(cfg)
        sspecs = {"params": pspecs, "opt": {"m": pspecs, "v": pspecs,
                                            "step": P()}}
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
        }
        bspecs = sh.batch_specs(cfg, batch, mesh)
        with sh.activation_sharding(sh.default_activation_specs(cfg, mesh, "train")):
            fn = train_loop.make_train_step(cfg)
            lowered = jax.jit(
                fn,
                in_shardings=(sh.named(mesh, sspecs), sh.named(mesh, bspecs)),
            ).lower(state, batch)
            lowered.compile()
        out["lowered"].append(arch)
    print("RESULT:" + json.dumps(out))
    """
)


@pytest.mark.slow
def test_sharding_rules_and_multidevice_compile():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    assert set(out["specs_ok"]) == set(all_arch_ids())
    assert out["lowered"] == ["qwen3-14b", "grok-1-314b", "rwkv6-3b"]


def test_activation_constrain_noop_without_context():
    import jax.numpy as jnp

    from repro.parallel.sharding import constrain

    x = jnp.ones((4, 4))
    assert constrain(x, "residual") is x


def test_dp_axes_and_pick():
    """Divisibility chooser degrades to replication, never fails."""
    from repro.parallel.sharding import pick

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    m = FakeMesh()
    assert pick(m, 32, "model") == "model"
    assert pick(m, 20, "model") is None  # 20 heads on 16-way TP -> replicate
    assert pick(m, 20, "model", ("data",)) is None
    assert pick(m, 512, ("data", "model")) == ("data", "model")


@pytest.mark.slow
def test_halo_shift_matches_baseline_on_sharded_mesh():
    """halo_shift exchanges only the boundary column over `model`; outputs
    must equal the plain shift exactly on a sequence-sharded mesh."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config, SHAPES
        from repro.models import registry
        from repro.parallel import sharding as sh
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg0 = get_config("rwkv6-3b", reduced=True)
        params = registry.init_params(cfg0, jax.random.PRNGKey(0))
        batch = registry.make_batch(cfg0, SHAPES["train_4k"],
                                    batch_override=2, seq_override=16)
        outs = {}
        for halo in (False, True):
            cfg = cfg0.replace(halo_shift=halo)
            with sh.activation_sharding(
                sh.default_activation_specs(cfg, mesh, "train")):
                fn = jax.jit(lambda p, b: registry.forward(p, cfg, b)[0])
                outs[halo] = np.asarray(fn(params, batch))
        err = float(np.max(np.abs(outs[True] - outs[False])))
        assert err < 1e-4, err
        print("RESULT:ok", err)
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "RESULT:ok" in proc.stdout

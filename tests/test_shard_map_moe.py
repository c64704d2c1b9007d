"""Multi-device equivalence of the shard_map expert-parallel MoE.

The shard_map path (explicit local dispatch + psum combine) must compute
the same loss AND gradients as the pure-GSPMD path with matching
block-local capacity. A 16x error in the router gradient (double-psum) or
a dropped expert contribution would pass single-device tests — so this
runs in a subprocess with 8 forced host devices on a (2, 4) mesh.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

# LM-zoo/trainer tests: tier-2 only (run with plain `pytest`)
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import build, get_config, runtime
    from repro.models.sharding import param_shardings

    # Auto axes: the GSPMD path constrains with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes refuse
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def run(impl):
        cfg = get_config("olmoe_1b_7b", reduced=True).with_(
            dtype="float32", moe_impl=impl, moe_dp_blocks=2, kv_groups=4)
        model = build(cfg)
        params = model.init(jax.random.key(0))
        B, S = 4, 16
        key = jax.random.key(1)
        batch = {
            "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32),
        }

        def loss_fn(p, b):
            loss, m = model.loss(p, b)
            return loss

        with mesh, runtime.use_mesh(mesh if impl == "shard_map" else None):
            p_sh = param_shardings(cfg, mesh, params)
            b_sh = jax.tree.map(
                lambda l: NamedSharding(mesh, P("data", None)), batch)
            g = jax.jit(jax.value_and_grad(loss_fn),
                        in_shardings=(p_sh, b_sh))(params, batch)
        loss, grads = g
        flat = jax.tree.leaves(grads)
        return float(loss), [float(jnp.linalg.norm(x.astype(jnp.float32)))
                             for x in flat]

    l1, g1 = run("gspmd")
    l2, g2 = run("shard_map")
    print("RESULT " + json.dumps({"l1": l1, "l2": l2, "g1": g1, "g2": g2}))
""")


def test_shard_map_moe_matches_gspmd_on_8_devices():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    assert abs(r["l1"] - r["l2"]) < 1e-4 * max(1.0, abs(r["l1"])), r
    g1, g2 = np.asarray(r["g1"]), np.asarray(r["g2"])
    np.testing.assert_allclose(g1, g2, rtol=2e-3, atol=1e-5)

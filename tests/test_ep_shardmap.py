"""EP shard_map data plane vs dense reference — runs in a subprocess with
8 forced host devices (the flag must not leak into this test process)."""
import pathlib
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import ep as EP
from repro.core.plan import static_plan
from repro.core.scaler import scale_layer
from repro.core.placer import place_layer
from repro.launch.mesh import make_serving_mesh

E, D, F, TOPK = 4, 32, 64, 2
mesh = make_serving_mesh(8, ep=2, tp=2, data=2)
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 5)
x = jax.random.normal(ks[0], (4, 8, D), jnp.float32)
rw = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.3
wg = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
wu = jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.1
wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.1

logits = x @ rw
tw, ti = jax.lax.top_k(logits, TOPK)
tw = jax.nn.softmax(tw, -1)
ref = jnp.zeros_like(x)
for e in range(E):
    fe = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
    for k in range(TOPK):
        ref += jnp.where((ti[..., k] == e)[..., None],
                         tw[..., k:k+1] * fe, 0.0)

plans = [
    static_plan(E, 2),
    place_layer(np.array([100., 10, 10, 10]),
                scale_layer(np.array([100., 10, 10, 10]),
                            max_total_replicas=6), 2),
]
for plan in plans:
    tables = EP.plan_to_tables(plan, ep=2, slots_per_device=4)
    with mesh:
        slot_w = EP.materialise_slots(
            {"w_gate": wg, "w_up": wu, "w_down": wd},
            tables["slot_expert"], mesh)
        y, m = EP.moe_ep_layer(
            x, rw, slot_w, tables, mesh=mesh, num_experts=E, top_k=TOPK,
            slots_per_device=4, capacity_factor=2.0)
    assert float(jnp.abs(y - ref).max()) < 1e-4
    expected = np.asarray(jnp.bincount(ti.reshape(-1), length=E))
    assert (np.asarray(m["expert_load"]) == expected).all()
    assert float(m["dropped"]) == 0.0
print("OK")
"""


@pytest.mark.slow
def test_ep_layer_matches_dense_reference():
    root = pathlib.Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # without this the child probes for a TPU backend and burns
             # minutes in GCP-metadata retries before falling back to CPU
             "JAX_PLATFORMS": "cpu"}, timeout=500)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout

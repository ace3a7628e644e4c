"""Multi-rank EP serving parity (tier-1): the serving hot path on a
genuine multi-device (data, ep, tp) mesh, forced via
--xla_force_host_platform_device_count in a subprocess (the flag must
not leak into this test process).

One subprocess drives every check (compilation is the dominant cost, so
the scenarios share a process) and prints KEY=VALUE markers:

  * engine greedy tokens bit-identical between a (1,1,1) and a (1,4,1)
    mesh with expert_runtime="on", prefill+decode (and (1,4,2) with
    tp splitting the FFN width);
  * runtime cold/warm/prewarm counts, bytes_moved, and GB-s at ep=4
    exactly equal the analytic ServerlessExpertPool;
  * an unchanged plan moves 0 bytes on every rank;
  * forced-overflow kept sets at ep=4 equal the ep=1 reference
    (global-capacity GShard rank: keep/drop is mesh-invariant);
  * slot-geometry padding when total_slots % ep != 0 (masked pad
    slots, warned, data plane still exact);
  * the double-buffered banks equal a single-buffered runtime's banks
    after a plan-churn sequence (pending catch-up correctness);
  * the Pallas paged decode attention, shard_mapped over the ep=4 mesh's
    rows, equals the unmapped kernel.
"""
import pathlib
import re
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, math, warnings
import jax, jax.numpy as jnp, numpy as np

from repro.configs import get_config
from repro.core.control import MOELESS_EXEC_TIME, ControlPlane, PlanEvent
from repro.core.plan import static_plan
from repro.distributed import ep as EP
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.expert_runtime import ExpertRuntime
from repro.serving.scheduler import GenRequest

assert len(jax.devices()) == 8
mesh1 = make_serving_mesh(1, ep=1)
mesh4 = make_serving_mesh(4, ep=4)
mesh42 = make_serving_mesh(8, ep=4, tp=2)

# ---- engine parity: same trace, (1,1,1) vs (1,4,1) vs (1,4,2) --------
cfg = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
# ample capacity: bit-exact parity is asserted drop-free (under drops
# the two paths agree only to float tolerance — different sum order)
cfg = cfg.with_(moe=dataclasses.replace(
    cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
params = M.init_params(cfg, jax.random.PRNGKey(0))

def make_requests(n=3, prompt_len=8, max_new=4):
    rng = np.random.default_rng(7)
    return [GenRequest(
        rid=i, arrival=0.05 * i,
        prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32),
        max_new_tokens=max_new) for i in range(n)]

def serve_on(mesh):
    reqs = make_requests()
    eng = ServingEngine(cfg, params, max_len=32, expert_runtime="on",
                        mesh=mesh)
    ctl = ControlPlane(cfg, "moeless", num_devices=8,
                       max_replicas_per_device=2)
    res = eng.serve(reqs, num_slots=3, control=ctl)
    toks = {r.rid: tuple(r.tokens) for r in reqs}
    return toks, res, ctl

toks1, res1, _ = serve_on(mesh1)
toks4, res4, ctl4 = serve_on(mesh4)
toks42, res42, _ = serve_on(mesh42)
print("PARITY_EP4=", int(toks1 == toks4), sep="")
print("PARITY_EP4_TP2=", int(toks1 == toks42), sep="")
print("SAME_ITERS=", int(res1.iterations == res4.iterations), sep="")

# ---- paged KV + chunked prefill parity at ep=4 -----------------------
# same trace through the paged pool with chunked prefill folded into the
# batched decode step, expert runtime on: tokens must equal the solo-
# prefill contiguous ep=1 reference bit-for-bit (drop-free capacity)
from repro.configs import ServingSpec
reqs_p = make_requests()
eng_p = ServingEngine(cfg, params, max_len=32, expert_runtime="on",
                      mesh=mesh4,
                      serving=ServingSpec(kv="paged", kv_block=5,
                                          prefill_chunk=3,
                                          prefix_cache=True))
ctl_p = ControlPlane(cfg, "moeless", num_devices=8,
                     max_replicas_per_device=2)
eng_p.serve(reqs_p, num_slots=3, control=ctl_p)
toks_p = {r.rid: tuple(r.tokens) for r in reqs_p}
print("PARITY_PAGED_CHUNKED_EP4=", int(toks_p == toks1), sep="")

# ---- runtime meters at ep=4 == analytic pool exactly -----------------
rt = res4.runtime
pool_counts = (
    sum(p.stats.cold_starts for p in ctl4.bal.pools.values()),
    sum(p.stats.warm_starts for p in ctl4.bal.pools.values()),
    sum(p.stats.prewarmed for p in ctl4.bal.pools.values()))
print("COUNTS_MATCH=", int(rt.stats.counts() == pool_counts), sep="")
print("BYTES_MATCH=", int(
    rt.stats.bytes_moved
    == rt.stats.transfers * rt.coeffs.expert_bytes), sep="")
print("RANK_BYTES_SUM=", int(
    abs(sum(rt.stats.rank_bytes.values()) - rt.stats.bytes_moved)
    < 1e-6), sep="")
end = res4.clock_s + 1.0
gb_pool = sum(p.finalize(end).instance_seconds_gb
              for p in ctl4.bal.pools.values())
gb_rt = rt.finalize(end).instance_seconds_gb
print("GBS_MATCH=", int(abs(gb_rt - gb_pool) <= 1e-9 * abs(gb_pool)),
      sep="")
# overlap meters: eligible copies are replicas absent from the served
# plan (consumed only next iteration — cold OR prewarmed ahead-of-time
# copies); bootstrap copies (served == plan) are exposed.  The split is
# exact and both lanes must be populated over a churny serve.
print("OVERLAP_SPLIT=", int(
    rt.stats.overlap_eligible_copies + rt.stats.exposed_copies
    == rt.stats.transfers), sep="")
print("OVERLAP_BOTH_LANES=", int(
    rt.stats.overlap_eligible_copies > 0
    and rt.stats.exposed_copies > 0), sep="")
print("OVERLAP_HIDDEN_POS=", int(rt.stats.overlap_hidden_s > 0), sep="")

# ---- unchanged plan moves 0 bytes per rank at ep=4 -------------------
rt4 = ExpertRuntime(cfg, params, num_devices=8, slots_per_device=2,
                    mesh=mesh4, keep_alive=1e9)
plan = static_plan(cfg.moe.num_experts, 8)
events = [PlanEvent(plan=plan, served=plan, lead_time=math.inf,
                    exec_time=MOELESS_EXEC_TIME)
          for _ in range(rt4.n_layers)]
r1 = rt4.apply(0.0, events)
r2 = rt4.apply(1.0, events)
print("FIRST_APPLY_RANKED=", int(
    r1.transfers > 0
    and abs(sum(r1.rank_bytes.values()) - r1.bytes_moved) < 1e-6),
    sep="")
print("UNCHANGED_ZERO_PER_RANK=", int(
    r2.transfers == 0
    and all(v == 0.0 for v in r2.rank_bytes.values())), sep="")

# ---- double-buffer catch-up == single-buffer banks -------------------
rt_db = ExpertRuntime(cfg, params, num_devices=8, slots_per_device=2,
                      mesh=mesh4, keep_alive=1e9)
rt_sb = ExpertRuntime(cfg, params, num_devices=8, slots_per_device=2,
                      mesh=mesh4, keep_alive=1e9, double_buffer=False)
E8 = cfg.moe.num_experts
plans = [static_plan(E8, 8)]
rng = np.random.default_rng(3)
for _ in range(3):   # churn: replicas move between devices
    loads = rng.integers(1, 100, size=E8).astype(np.float64)
    from repro.core.scaler import scale_layer
    from repro.core.placer import place_layer
    plans.append(place_layer(loads, scale_layer(
        loads, max_total_replicas=12), 8, prev=plans[-1]))
for i, p in enumerate(plans):
    ev = [PlanEvent(plan=p, served=p, lead_time=math.inf,
                    exec_time=MOELESS_EXEC_TIME)
          for _ in range(rt_db.n_layers)]
    rt_db.apply(float(i), ev)
    rt_sb.apply(float(i), ev)
same = all(
    bool(jnp.array_equal(a, b))
    for sa, sb in zip(rt_db.ep_state(), rt_sb.ep_state())
    if sa is not None
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)))
print("DOUBLE_BUFFER_BANKS_EQUAL=", int(same), sep="")

# ---- slot-geometry padding: total_slots % ep != 0 --------------------
with warnings.catch_warnings(record=True) as wlog:
    warnings.simplefilter("always")
    rt_pad = ExpertRuntime(cfg, params, num_devices=5,
                           slots_per_device=2, mesh=mesh4,
                           keep_alive=1e9)
warned = any("masked slot" in str(w.message) for w in wlog)
j0 = rt_pad.moe_positions[0]
bank_slots = next(iter(rt_pad.banks[j0].values())).shape[1]
rt_pad.bootstrap()
tables_ok = int(rt_pad.table_slots.max() < rt_pad.total_slots)
print("PAD_GEOMETRY=", int(
    warned and rt_pad.total_slots == 10 and rt_pad.phys_slots == 12
    and rt_pad.pad_slots == 2 and bank_slots == 12 and tables_ok),
    sep="")

# ---- forced overflow: kept sets at ep=4 equal the ep=1 reference -----
E, D, F, TOPK = 4, 16, 32, 2
ks = jax.random.split(jax.random.PRNGKey(1), 5)
rw = jax.random.normal(ks[0], (D, E), jnp.float32) * 0.2
rw = rw.at[:, 0].add(1.0)      # skewed router -> expert 0 overflows
wg = jax.random.normal(ks[1], (E, D, F), jnp.float32) * 0.1
wu = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
wd = jax.random.normal(ks[3], (E, F, D), jnp.float32) * 0.1
weights = {"w_gate": wg, "w_up": wu, "w_down": wd}
x = jax.random.normal(ks[4], (8, 4, D), jnp.float32)   # B=8 % 4 == 0

plan = static_plan(E, 4)
tables = EP.plan_to_tables(plan, ep=4, slots_per_device=2,
                           num_devices=4)
CF = 0.5   # forces drops: cap = ceil(0.5 * 2 * 32 / 4) = 8 < load(e0)
outs = {}
for name, mesh, sd in (("ep1", mesh1, 8), ("ep4", mesh4, 2)):
    with mesh:
        sw = EP.materialise_slots(weights, tables["slot_expert"], mesh)
        y, m = EP.moe_ep_layer(
            x, rw, sw, tables, mesh=mesh, num_experts=E, top_k=TOPK,
            slots_per_device=sd, capacity_factor=CF)
    outs[name] = (np.asarray(y), np.asarray(m["expert_load"]),
                  float(m["dropped"]))
y1, l1, d1 = outs["ep1"]
y4, l4, d4 = outs["ep4"]
print("OVERFLOW_FORCED=", int(d1 > 0), sep="")
print("OVERFLOW_DROPS_EQUAL=", int(d1 == d4), sep="")
print("OVERFLOW_LOADS_EQUAL=", int((l1 == l4).all()), sep="")
# identical tables + identical global GShard ranks => identical kept
# sets; the combine sums the same contributions in the same sorted
# order, so the outputs agree bitwise
print("OVERFLOW_Y_EQUAL=", int(np.array_equal(y1, y4)), sep="")
print("OVERFLOW_Y_CLOSE=", int(np.allclose(y1, y4, atol=1e-6)), sep="")

# dispatch_moe drop-equivalence at ep=4 (single-replica plan)
from repro.models.moe import dispatch_moe
yd, md = dispatch_moe(
    {"router": {"w_gate": rw}, "experts": weights},
    x.reshape(1, -1, D), top_k=TOPK, num_experts=E, capacity_factor=CF)
print("DISPATCH_DROPS_EQUAL=", int(float(md["dropped"]) == d4), sep="")

# ---- Pallas paged decode attention mapped over the ep=4 mesh ---------
# a Mosaic kernel cannot be partitioned by the compiler, so on a serving
# mesh attention_block shard_maps it over the rows; the rows' outputs
# must match the unmapped kernel's (to f32 rounding: the output
# projection then runs on row shards, a wrong row or table is O(1) off)
from repro.models import layers as L
acfg = cfg.with_(impl="pallas_interpret")
ap = jax.tree.map(lambda a: a[0], params["layers"][0]["attn"])
blk, nbs, B = 4, 3, 4
nb = 1 + B * nbs
kk = jax.random.split(jax.random.PRNGKey(11), 3)
pool = L.init_paged_attn_cache(acfg, nb, blk, jnp.float32)
pool["k"] = jax.random.normal(kk[0], pool["k"].shape)
pool["v"] = jax.random.normal(kk[1], pool["v"].shape)
tab = np.arange(1, nb).reshape(B, nbs)
pos = np.zeros((nb, blk), np.int32)
for r in range(B):
    pos[tab[r]] = np.arange(nbs * blk).reshape(nbs, blk)
pool["pos"] = jnp.asarray(pos)
clen = jnp.asarray([3, 7, 0, 10], jnp.int32)
xa = jax.random.normal(kk[2], (B, 1, acfg.d_model))
outs = [L.attention_block(ap, acfg, xa, clen[:, None], cache=pool,
                          cache_len=clen, impl=acfg.impl,
                          block_tables=jnp.asarray(tab, jnp.int32),
                          new_counts=jnp.ones(B, jnp.int32), mesh=m)[0]
        for m in (None, mesh4)]
print("ATTN_ON_MESH=", int(np.allclose(np.asarray(outs[0]),
                                       np.asarray(outs[1]),
                                       rtol=0, atol=1e-5)), sep="")
print("DONE")
"""


@pytest.fixture(scope="module")
def markers():
    root = pathlib.Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # without this the child probes for a TPU backend and burns
             # minutes in GCP-metadata retries before falling back to CPU
             "JAX_PLATFORMS": "cpu"}, timeout=560)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "DONE" in r.stdout, r.stdout[-4000:] + r.stderr[-4000:]
    return dict(re.findall(r"^([A-Z_0-9]+)=(\S+)$", r.stdout, re.M))


def test_engine_tokens_bit_identical_ep4(markers):
    assert markers["PARITY_EP4"] == "1"
    assert markers["SAME_ITERS"] == "1"


def test_engine_tokens_ep4_tp2(markers):
    assert markers["PARITY_EP4_TP2"] == "1"


def test_paged_chunked_tokens_bit_identical_ep4(markers):
    assert markers["PARITY_PAGED_CHUNKED_EP4"] == "1"


def test_runtime_meters_match_analytic_pool_at_ep4(markers):
    assert markers["COUNTS_MATCH"] == "1"
    assert markers["BYTES_MATCH"] == "1"
    assert markers["GBS_MATCH"] == "1"
    assert markers["RANK_BYTES_SUM"] == "1"


def test_overlap_meters(markers):
    assert markers["OVERLAP_SPLIT"] == "1"
    assert markers["OVERLAP_BOTH_LANES"] == "1"
    assert markers["OVERLAP_HIDDEN_POS"] == "1"


def test_unchanged_plan_moves_zero_bytes_per_rank(markers):
    assert markers["FIRST_APPLY_RANKED"] == "1"
    assert markers["UNCHANGED_ZERO_PER_RANK"] == "1"


def test_double_buffer_banks_equal_single_buffer(markers):
    assert markers["DOUBLE_BUFFER_BANKS_EQUAL"] == "1"


def test_slot_geometry_padding(markers):
    assert markers["PAD_GEOMETRY"] == "1"


def test_forced_overflow_kept_sets_equal_ep1_reference(markers):
    assert markers["OVERFLOW_FORCED"] == "1"
    assert markers["OVERFLOW_DROPS_EQUAL"] == "1"
    assert markers["OVERFLOW_LOADS_EQUAL"] == "1"
    assert markers["OVERFLOW_Y_CLOSE"] == "1"


def test_forced_overflow_outputs_bitwise_equal(markers):
    assert markers["OVERFLOW_Y_EQUAL"] == "1"


def test_dispatch_drop_equivalence_at_ep4(markers):
    assert markers["DISPATCH_DROPS_EQUAL"] == "1"


def test_pallas_attention_rows_on_ep4_mesh(markers):
    assert markers["ATTN_ON_MESH"] == "1"

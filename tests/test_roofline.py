"""HLO walker validation: the while-multiplied dot-FLOP count must match the
same computation with the loop unrolled (where XLA's own cost_analysis is
correct), and collective accounting must scale with trip count.

Runs in a subprocess so the forced device count stays out of this process.
"""
import json
import subprocess
import sys
import textwrap

SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import make_mesh_compat
    from repro.roofline.analysis import HloModule

    mesh = make_mesh_compat((2, 4), ("data", "model"), devices=jax.devices())
    L, D, F = 6, 64, 256

    def body(h, w):
        w1, w2 = w
        return jnp.tanh(h @ w1) @ w2, None

    def scanned(h, stack):
        return jax.lax.scan(body, h, stack)[0].astype(jnp.float32).mean()

    def unrolled(h, stack):
        return jax.lax.scan(body, h, stack, unroll=L)[0].astype(jnp.float32).mean()

    h = jax.ShapeDtypeStruct((16, D), jnp.bfloat16)
    stack = (jax.ShapeDtypeStruct((L, D, F), jnp.bfloat16),
             jax.ShapeDtypeStruct((L, F, D), jnp.bfloat16))
    sh = (NamedSharding(mesh, P("data", None)),
          (NamedSharding(mesh, P(None, None, "model")),
           NamedSharding(mesh, P(None, "model", None))))

    out = {}
    for name, fn in [("scanned", scanned), ("unrolled", unrolled)]:
        comp = jax.jit(fn, in_shardings=sh,
                       out_shardings=NamedSharding(mesh, P())).lower(h, stack).compile()
        mod = HloModule(comp.as_text(), trip_hints=[L])
        c = mod.entry_cost()
        ca = comp.cost_analysis()
        out[name] = {"flops": c.flops, "coll": c.collective_bytes,
                     "xla_flops": ca.get("flops")}
    print("RESULT" + json.dumps(out))
""")


def test_walker_matches_unrolled_ground_truth():
    out = subprocess.run([sys.executable, "-c", SNIPPET], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "RESULT" in out.stdout, out.stderr[-2000:]
    data = json.loads(out.stdout.split("RESULT")[1])
    scanned, unrolled = data["scanned"], data["unrolled"]
    # walker on the rolled loop ~= walker on the unrolled program
    assert scanned["flops"] == __import__("pytest").approx(
        unrolled["flops"], rel=0.05)
    # analytic matmul ground truth: L layers x 2 dots, per chip
    L, D, F, B_loc, F_loc = 6, 64, 256, 16 // 2, 256 // 4
    analytic = L * 2 * (2 * B_loc * D * F_loc)
    assert scanned["flops"] == __import__("pytest").approx(analytic, rel=0.01)
    # XLA (where correct, i.e. unrolled) counts dots PLUS elementwise, so it
    # upper-bounds the walker's dot-only number
    assert unrolled["xla_flops"] >= scanned["flops"]
    assert unrolled["xla_flops"] <= scanned["flops"] * 2.5
    # XLA undercounts the rolled program (body counted once) — the bug the
    # walker exists to fix
    assert scanned["xla_flops"] < scanned["flops"] / 2
    # collectives also scale with the trip count
    assert scanned["coll"] == __import__("pytest").approx(unrolled["coll"], rel=0.05)


def test_shape_parsing_helpers():
    from repro.roofline.analysis import _all_shapes, _nbytes, _parse_shape

    assert _parse_shape("bf16[16,4096]{1,0} fusion(...)") == ("bf16", [16, 4096])
    assert _nbytes(("f32", [8, 4])) == 128
    shapes = _all_shapes("(s32[], bf16[32,64]{1,0}, f32[4,256,64])")
    assert ("bf16", [32, 64]) in shapes and ("f32", [4, 256, 64]) in shapes


def test_model_flops_moe_counts_active_only():
    from repro.configs import SHAPES, all_configs
    from repro.roofline.analysis import active_params

    cfg = all_configs()["qwen3-moe-30b-a3b"]
    total = cfg.param_count()
    active = active_params(cfg)
    assert active < total / 5  # 8-of-128 experts
    dense = all_configs()["yi-9b"]
    assert active_params(dense) == dense.param_count()

"""The grouped product of the expert layers (ops/pallas/grouped_matmul.py,
interpreted on the CPU backend) against a loop over the experts in
float32 and against `jax.lax.ragged_dot`; its gradient through
`experts_apply`; its tiles; what it tells the step log; and the three
expert configurations' programs lowered with it under their op's scope.
(Compiled for the chip: tests/test_paged_attention_op.py, the one file
that loads the TPU's compiler.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark import manifest
from paddle_tpu import monitor
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.parallel import moe


def loop(lhs, rhs, sizes):
    """Row r of group g is lhs[r] @ rhs[g], float32, expert by expert."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    row = 0
    for g, n in enumerate(sizes):
        out[row:row + n] = lhs[row:row + n] @ rhs[g]
        row += n
    return out


# m, k, n, group sizes, bytes a weight block may take (None: the module's)
CASES = {
    "empty_first": (48, 32, 40, [0, 5, 20, 7], None),
    "empty_middle": (48, 32, 40, [9, 0, 0, 30], None),
    "empty_last": (48, 32, 40, [3, 40, 0], None),
    "all_empty": (48, 32, 40, [0, 0, 0, 0], None),
    "one_group_holds_every_row": (48, 32, 40, [0, 48, 0], None),
    "rows_past_the_sum": (64, 32, 40, [2, 0, 11, 4], None),
    # tiles of 128 rows, the first group in three of them
    "group_longer_than_a_row_tile": (300, 16, 24, [290, 10], None),
    # k = 33 is no multiple of anything, n = 200 in blocks of 128 lanes
    "k_n_that_no_tile_divides": (40, 33, 200, [7, 0, 21, 12],
                                 128 * 33 * 2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_against_a_loop_over_experts_and_ragged_dot(case, dtype,
                                                    monkeypatch):
    m, k, n, sizes, block_bytes = CASES[case]
    if block_bytes:
        monkeypatch.setattr(gm, "WEIGHT_TILE_BYTES", block_bytes)
        assert gm.tiles(m, k, n, dtype)[2] == 128 < n
    rng = np.random.default_rng(len(case))
    lhs = rng.normal(size=(m, k)).astype(np.float32)
    rhs = rng.normal(size=(len(sizes), k, n)).astype(np.float32)
    led = sum(sizes)
    # what no group holds is never read: poison it
    lhs[led:] = np.nan
    lhs, rhs = jnp.asarray(lhs, dtype), jnp.asarray(rhs, dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, group_sizes)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    got = np.asarray(got)[:led]
    assert np.isfinite(got).all()
    clean = jnp.where(jnp.arange(m)[:, None] < led, lhs, 0)
    ragged = jax.lax.ragged_dot(clean, rhs, group_sizes,
                                preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got, np.asarray(ragged)[:led],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, loop(clean, rhs, sizes)[:led],
                               rtol=1e-4, atol=1e-4)


def test_the_walk_visits_held_groups_and_led_tiles_only():
    """Groups of 0, 20, 0, 3, 0 rows in tiles of 16: group 1 in tiles 0
    and 1, group 3 in tile 1; three visits, whatever M is."""
    group, tile, starts, ends, count = gm.visits(
        jnp.asarray([0, 20, 0, 3, 0], jnp.int32), 4096, 16)
    assert int(count[0]) == 3
    assert group.shape == tile.shape == (4096 // 16 + 4,)
    assert np.asarray(group)[:3].tolist() == [1, 1, 3]
    assert np.asarray(tile)[:3].tolist() == [0, 1, 1]
    assert np.asarray(starts).tolist() == [0, 0, 20, 20, 23]
    assert np.asarray(ends).tolist() == [0, 20, 20, 23, 23]
    # the unread rest names blocks that exist
    assert 0 <= int(group.min()) and int(group.max()) <= 4
    assert 0 <= int(tile.min()) and int(tile.max()) < 256


# the products the three expert cells run: d_in, f of w1, d_out, rows of
# a decode step and of a prefill step (slots x tokens x top_k), and the
# tiles they get: an expert's matrix in one block where it is under
# 16 MiB, in four and two where it is 59 and 29 MB
@pytest.mark.parametrize("k,n,m,want", [
    (1024, 2688, 1408, (128, 1024, 2688)),
    (2688, 1024, 22528, (128, 2688, 1024)),
    (7168, 4096, 512, (128, 7168, 1024)),
    (2048, 7168, 8192, (128, 2048, 3584)),
    (2304, 2048, 1024, (128, 2304, 2048)),
    (1024, 2304, 16384, (128, 1024, 2304))],
    ids=["nemotron_w1_decode", "nemotron_w2_prefill", "kimi_k2_5_w1_decode",
         "kimi_k2_5_w2_prefill", "kimi_linear_w1_decode",
         "kimi_linear_w2_prefill"])
def test_tiles_follow_from_shapes_and_type(k, n, m, want):
    assert gm.tiles(m, k, n, jnp.bfloat16) == want
    tn = want[2]
    assert tn == n or (tn % 128 == 0
                       and k * tn * 2 <= gm.WEIGHT_TILE_BYTES)
    # a short lhs is one tile of whole sublane tiles: 16 rows of
    # bfloat16, 8 of float32
    assert gm.tiles(20, k, n, jnp.bfloat16)[0] == 32
    assert gm.tiles(20, k, n, jnp.float32)[0] == 24


@pytest.mark.parametrize("sizes,biased", [([4, 0, 9, 6], False),
                                          ([4, 0, 14, 6], True)],
                         ids=["rows_past_the_sum", "full_groups_with_biases"])
def test_gradient_of_experts_apply_is_ragged_dots(sizes, biased):
    """A scalar of `experts_apply` differentiated through the kernel's
    `custom_vjp` against the same layer written with `ragged_dot`: as
    the routed layers call it (no bias, rows past the groups' sum, whose
    result is undefined and masked) and as `moe_ffn_sparse` does
    (biases, every row in a group)."""
    rng = np.random.default_rng(3)
    led = (jnp.arange(24) < sum(sizes))[:, None]
    sizes = jnp.asarray(sizes, jnp.int32)
    args = [jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
            for shape in [(24, 16), (4, 16, 20), (4, 20, 12)]
            + [(4, 20), (4, 12)] * biased]

    def with_ragged_dot(rows, w1, w2, *b):
        def bias(k):
            return jnp.repeat(b[k], sizes, axis=0, total_repeat_length=24) \
                if b else 0.0
        h = jax.lax.ragged_dot(rows, w1, sizes) + bias(0)
        return jax.lax.ragged_dot(jax.nn.gelu(h), w2, sizes) + bias(1)

    def with_the_kernel(rows, w1, w2, *b):
        return moe.experts_apply(rows, sizes, w1, w2, jax.nn.gelu, *b)

    def scalar(layer):
        return lambda *a: jnp.sum(jnp.where(led, jnp.sin(layer(*a)), 0.0))

    which = range(len(args))
    want_value, want = jax.value_and_grad(
        scalar(with_ragged_dot), argnums=which)(*args)
    value, got = jax.jit(jax.value_and_grad(
        scalar(with_the_kernel), argnums=which))(*args)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_step_log_names_what_a_product_lowered_to():
    """`moe.grouped_product`: a flight record a product lowered, with
    its tiles (forward) or `ragged_dot` (the backward's), and the
    monitor's counter of that name; nothing when a compiled step runs
    again."""
    rows = jnp.ones((32, 16), jnp.bfloat16)
    w1 = jnp.ones((4, 16, 24), jnp.bfloat16)
    w2 = jnp.ones((4, 24, 16), jnp.bfloat16)
    sizes = jnp.asarray([8, 0, 8, 1], jnp.int32)
    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats("moe.grouped_product")
    monitor.reset_flight_recorder()
    try:
        layer = jax.jit(lambda r: moe.experts_apply(
            r, sizes, w1, w2, jax.nn.relu))
        layer(rows)
        layer(rows)
        records = [r["lowered"] for r in monitor.flight_records()
                   if r["kind"] == "moe.grouped_product"]
        assert records == ["pallas[32, 16, 24]", "pallas[32, 24, 16]"]
        counters = monitor.get_stats_snapshot()["counters"]
        assert counters["moe.grouped_product"] == 2
        jax.grad(lambda r: jnp.sum(moe.experts_apply(
            r.astype(jnp.bfloat16), sizes, w1, w2, jax.nn.relu)[:17]))(
                jnp.ones((32, 16), jnp.float32))
        records = [r["lowered"] for r in monitor.flight_records()
                   if r["kind"] == "moe.grouped_product"]
        assert records[2:] == ["pallas[32, 16, 24]", "pallas[32, 24, 16]",
                               "ragged_dot", "ragged_dot"]
    finally:
        fluid.set_flags({"FLAGS_enable_monitor": prev})
        monitor.reset_stats("moe.grouped_product")
        monitor.reset_flight_recorder()


def eqns(jaxpr, scope=""):
    """(primitive, name stack from the step's root) of every equation,
    those of nested jaxprs under their equation's stack."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from eqns(inner, here)


@pytest.mark.parametrize("cell,family,op,expert_layers", [
    ("nemotron3_super_ep4_l11.batch_reason", "hybrid_serve", "latent_moe", 2),
    ("kimi_k2_5_ep32_l5.batch_long_ctx", "mla_serve", "gated_moe", 2),
    ("kimi_linear_ep8_l8.batch_rollout", "kda_serve", "gated_moe", 3)],
    ids=["nemotron3_super", "kimi_k2_5", "kimi_linear"])
def test_expert_layers_lower_to_the_kernel_under_their_scope(
        cell, family, op, expert_layers):
    """The engine's decode and prefill programs at the rehearsal's
    sizes: two `grouped_matmul` kernels an expert layer, each under the
    `latent_moe:` / `gated_moe:` scope that `moe_ms_per_step.*` reads,
    and no `ragged_dot` anywhere."""
    import importlib
    from paddle_tpu.models import gpt
    build = importlib.import_module(f"benchmark.families.{family}").build
    _, cfg, _, _ = manifest.cell(cell, rehearsal=True)
    cfg = dict(cfg, engine=dict(cfg["engine"], max_slots=3))
    eng = build(cfg, {"timeout_ms": 600000}, 1, 2**31 + 36).engine
    gpt._ensure_decode_state(eng.scope, eng._prog.global_block(),
                             eng.step.cache_names + eng.step.state_names)
    for name, prog, feed, _ in eng.executables():
        step_fn, state, feeds = eng.exe._resolve_step(
            prog, feed, eng.fetch_list(prog), eng.scope, None)
        found = list(eqns(jax.make_jaxpr(step_fn.fn)(
            state, feeds, np.uint32(0)).jaxpr))
        assert not [p for p, _ in found if "ragged" in p], name
        kernels = [s for p, s in found
                   if p == "pallas_call" and "grouped_matmul" in s]
        assert len(kernels) == 2 * expert_layers, (name, kernels)
        assert all(f"/{op}:0/" in s for s in kernels), (name, kernels)

"""2-process multi-host bootstrap rehearsal.

Reference analogue: test_dist_base.py:533-770 — multi-process localhost
training with loss-equivalence against single-process. Here each worker
process carries 4 virtual CPU devices; `init_parallel_env()` performs
the REAL `jax.distributed.initialize` coordinator handshake (trainer 0's
endpoint, the PADDLE_TRAINER_* env contract), then:

1. a global-mesh allreduce across both processes' devices, and
2. three dp train steps of the shared MLP through Executor +
   CompiledProgram.with_distributed, whose losses must match a
   single-process run of the same seeded program.

The single-process 8-device mesh in test_parallel.py covers the SPMD
math; this covers the process-bootstrap path those tests bypass.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MLP_SOURCE = '''
def build_and_run(fluid, layers, mesh=None, steps=3):
    import numpy as np
    rng = np.random.RandomState(0)
    xs = rng.randn(32, 16).astype(np.float32)
    ys = rng.randn(32, 1).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        label = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=32, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        prog = main
        if mesh is not None:
            prog = fluid.CompiledProgram(main).with_distributed(mesh)
        vals = []
        for _ in range(steps):
            lv, = exe.run(prog, feed={"x": xs, "y": ys},
                          fetch_list=[loss])
            vals.append(float(np.asarray(lv)))
    return vals
'''

_WORKER = f'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {ROOT!r})
import paddle_tpu as fluid
import paddle_tpu.distributed as dist
from paddle_tpu import layers

dist.init_parallel_env()   # PADDLE_TRAINER_* -> jax.distributed.initialize
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert dist.parallel_env_world_size() == 2
rank = dist.parallel_env_rank()

# 1. global-mesh allreduce: every device contributes its global index
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = dist.global_mesh({{"dp": -1}})
sh = NamedSharding(mesh, P("dp"))
local = np.arange(4, dtype=np.float32) + 4 * jax.process_index()
g = jax.make_array_from_process_local_data(sh, local, (8,))
total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(g)
total = float(np.asarray(total))
assert total == 28.0, f"allreduce over the global mesh got {{total}}"

# 2. dp train steps through the framework over the 2-process mesh
{_MLP_SOURCE}
vals = build_and_run(fluid, layers, mesh=mesh)
print("LOSSES", json.dumps(vals))
'''

_SINGLE = f'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {ROOT!r})
import paddle_tpu as fluid
from paddle_tpu import layers
{_MLP_SOURCE}
vals = build_and_run(fluid, layers, mesh=None)
print("LOSSES", json.dumps(vals))
'''


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_worker(code, env, timeout=420):
    e = dict(os.environ)
    e.pop("XLA_FLAGS", None)
    e["PYTHONPATH"] = ROOT
    e.update(env)
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=timeout,
                          env=e)


def _losses(proc, who):
    assert proc.returncode == 0, \
        f"{who} failed rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    for line in proc.stdout.splitlines():
        if line.startswith("LOSSES "):
            return json.loads(line[len("LOSSES "):])
    raise AssertionError(f"{who}: no LOSSES line\n{proc.stdout}")


def _spawn_pair(code, extra_env=None):
    """Run `code` in 2 coordinated worker processes; returns both procs."""
    import concurrent.futures as cf

    port = _free_port()
    eps = f"127.0.0.1:{port},127.0.0.1:{port + 1}"
    with cf.ThreadPoolExecutor(2) as pool:
        futs = [
            pool.submit(_run_worker, code,
                        {"PADDLE_TRAINERS_NUM": "2",
                         "PADDLE_TRAINER_ID": str(i),
                         "PADDLE_TRAINER_ENDPOINTS": eps,
                         **(extra_env or {})})
            for i in range(2)
        ]
        return [f.result() for f in futs]


def test_two_process_bootstrap_and_loss_parity():
    procs = _spawn_pair(_WORKER)
    l0 = _losses(procs[0], "worker 0")
    l1 = _losses(procs[1], "worker 1")
    np.testing.assert_allclose(l0, l1, rtol=1e-6,
                               err_msg="ranks disagree on the loss")

    single = _losses(_run_worker(_SINGLE, {}), "single-process")
    np.testing.assert_allclose(
        l0, single, rtol=1e-4, atol=1e-5,
        err_msg="2-process dp loss must match single-process")
    assert single[0] > single[-1], "loss must decrease over steps"


# ---------------------------------------------------------------------------
# scenario 2: dp x tp mesh whose TP groups SPAN the process boundary +
# an all-to-all-bearing (Ulysses) step across processes
# (test_dist_base.py:533-770 grinds the same matrix with NCCL rings)
# ---------------------------------------------------------------------------

_TRANSFORMER_SOURCE = '''
def build_and_run_transformer(fluid, layers, mesh=None, spec_fn=None,
                              steps=3):
    import numpy as np
    from paddle_tpu.models import transformer
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        dropout=0.0, use_flash=False, tp=mesh is not None)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 64, (4, 16)).astype(np.int64)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        loss, feeds = transformer.build_train(cfg, 4, 16, lr=1e-2)
        exe = fluid.Executor()
        exe.run(startup)
        prog = main
        if mesh is not None:
            prog = fluid.CompiledProgram(main).with_distributed(
                mesh, state_spec_fn=spec_fn, batch_axes=("dp",))
        vals = []
        for _ in range(steps):
            lv, = exe.run(prog, feed={"tokens": toks, "labels": toks},
                          fetch_list=[loss])
            vals.append(float(np.asarray(lv)))
    return vals


def tp_spec_fn(name):
    from jax.sharding import PartitionSpec as P
    if name.endswith((".q.w", ".k.w", ".v.w", ".fc1.w")):
        return P(None, "tp")
    if name.endswith((".q.b", ".k.b", ".v.b", ".fc1.b")):
        return P("tp")
    if name.endswith((".proj.w", ".fc2.w")):
        return P("tp", None)
    return None
'''

_WORKER_TP = f'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {ROOT!r})
import paddle_tpu as fluid
import paddle_tpu.distributed as dist
from paddle_tpu import layers

dist.init_parallel_env()
rank = dist.parallel_env_rank()
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# TP groups that CROSS the process boundary: device grid transposed so
# each tp pair is (process0_dev_i, process1_dev_i) — every q/k/v matmul
# psum rides the inter-process link, not just intra-host
devs = np.array(jax.devices()).reshape(2, 4).T      # [dp=4, tp=2]
mesh = Mesh(devs, axis_names=("dp", "tp"))
{_TRANSFORMER_SOURCE}
vals = build_and_run_transformer(fluid, layers, mesh=mesh,
                                 spec_fn=tp_spec_fn)
print("LOSSES", json.dumps(vals))

# Ulysses all-to-all attention across both processes: sp=8 spans the
# job; the two all-to-alls cross the process boundary
from paddle_tpu.parallel.ulysses import ulysses_attention_sharded
mesh_sp = Mesh(np.array(jax.devices()), axis_names=("sp",))
rng = np.random.RandomState(1)
b, h, t, d = 2, 8, 32, 8
qg = rng.randn(b, h, t, d).astype(np.float32)
kg = rng.randn(b, h, t, d).astype(np.float32)
vg = rng.randn(b, h, t, d).astype(np.float32)
sh = NamedSharding(mesh_sp, P(None, None, "sp", None))
half = slice(rank * t // 2, (rank + 1) * t // 2)
mk = lambda a: jax.make_array_from_process_local_data(
    sh, np.ascontiguousarray(a[:, :, half]), (b, h, t, d))
q, k, v = mk(qg), mk(kg), mk(vg)
out = ulysses_attention_sharded(q, k, v, mesh_sp, seq_axis="sp",
                                causal=True)
rep = jax.jit(lambda x: x,
              out_shardings=NamedSharding(mesh_sp, P()))(out)
got = np.asarray(rep)

# dense causal reference on the replicated host copies
s = np.einsum("bhqd,bhkd->bhqk", qg, kg) / np.sqrt(d)
mask = np.tril(np.ones((t, t), bool))
s = np.where(mask, s, -1e30)
w = np.exp(s - s.max(-1, keepdims=True))
w /= w.sum(-1, keepdims=True)
ref = np.einsum("bhqk,bhkd->bhqd", w, vg)
err = float(np.abs(got - ref).max())
assert err < 1e-4, f"ulysses cross-process mismatch {{err}}"
print("ULYSSES_OK", err)
'''


def test_cross_process_tp_and_alltoall():
    procs = _spawn_pair(_WORKER_TP)
    l0 = _losses(procs[0], "worker 0")
    l1 = _losses(procs[1], "worker 1")
    np.testing.assert_allclose(l0, l1, rtol=1e-6,
                               err_msg="ranks disagree on the loss")
    for i, p in enumerate(procs):
        assert "ULYSSES_OK" in p.stdout, \
            f"worker {i}: no ULYSSES_OK\n{p.stdout}\n{p.stderr}"

    # single-process reference of the same seeded transformer program
    single_code = f'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {ROOT!r})
import paddle_tpu as fluid
from paddle_tpu import layers
{_TRANSFORMER_SOURCE}
vals = build_and_run_transformer(fluid, layers, mesh=None)
print("LOSSES", json.dumps(vals))
'''
    single = _losses(_run_worker(single_code, {}), "single-process")
    np.testing.assert_allclose(
        l0, single, rtol=1e-4, atol=1e-5,
        err_msg="cross-process dp x tp loss must match single-process")


# ---------------------------------------------------------------------------
# scenario 3: sharded checkpoint written by 2 processes, loaded and
# resumed by 1 process (and vice-versa parity on the continued losses)
# ---------------------------------------------------------------------------

_WORKER_CKPT_TMPL = '''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {root!r})
import paddle_tpu as fluid
import paddle_tpu.distributed as dist
from paddle_tpu import layers
from paddle_tpu.io_sharded import save_sharded_persistables

dist.init_parallel_env()
mesh = dist.global_mesh({{"dp": -1}})
{mlp_source}
import numpy as np
rng = np.random.RandomState(0)
xs = rng.randn(32, 16).astype(np.float32)
ys = rng.randn(32, 1).astype(np.float32)
main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 7
with fluid.program_guard(main, startup):
    x = layers.data("x", shape=[16], dtype="float32")
    label = layers.data("y", shape=[1], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor()
    exe.run(startup)
    prog = fluid.CompiledProgram(main).with_distributed(mesh)
    pre, post = [], []
    for _ in range(3):
        lv, = exe.run(prog, feed={{"x": xs, "y": ys}}, fetch_list=[loss])
        pre.append(float(np.asarray(lv)))
    save_sharded_persistables(exe, {ckpt!r}, main_program=main,
                              scope=scope)
    for _ in range(3):
        lv, = exe.run(prog, feed={{"x": xs, "y": ys}}, fetch_list=[loss])
        post.append(float(np.asarray(lv)))
print("LOSSES", json.dumps(pre + post))
'''

_SINGLE_RESUME_TMPL = '''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {root!r})
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.io_sharded import load_sharded_persistables

rng = np.random.RandomState(0)
xs = rng.randn(32, 16).astype(np.float32)
ys = rng.randn(32, 1).astype(np.float32)
main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 99   # different init on purpose
with fluid.program_guard(main, startup):
    x = layers.data("x", shape=[16], dtype="float32")
    label = layers.data("y", shape=[1], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor()
    exe.run(startup)
    # resume from the 2-process sharded checkpoint in ONE process
    load_sharded_persistables(exe, {ckpt!r}, main_program=main,
                              scope=scope)
    vals = []
    for _ in range(3):
        lv, = exe.run(main, feed={{"x": xs, "y": ys}}, fetch_list=[loss])
        vals.append(float(np.asarray(lv)))
print("LOSSES", json.dumps(vals))
'''


def test_checkpoint_across_process_counts(tmp_path):
    ckpt = str(tmp_path / "ckpt_2proc")
    code = _WORKER_CKPT_TMPL.format(root=ROOT, mlp_source="",
                                    ckpt=ckpt)
    procs = _spawn_pair(code)
    l0 = _losses(procs[0], "worker 0")
    l1 = _losses(procs[1], "worker 1")
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    assert os.path.exists(os.path.join(ckpt, "manifest.json")), \
        "process 0 must write the primary manifest"

    resumed = _losses(
        _run_worker(_SINGLE_RESUME_TMPL.format(root=ROOT, ckpt=ckpt), {}),
        "single-process resume")
    # the single process resumed from the 2-process shards must continue
    # exactly where the 2-process run went (post-checkpoint losses)
    np.testing.assert_allclose(
        resumed, l0[3:], rtol=1e-4, atol=1e-6,
        err_msg="single-process resume diverges from the 2-process run")

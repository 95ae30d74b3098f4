"""`bert_train`: a BERT configuration trained through the program's
own path: models/transformer.build_train under the AMP rewrite, AdamW,
`Executor.run` with a feed each step; across chips through
`CompiledProgram.with_data_parallel` under FLAGS_sharded_exec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import manifest, weights

KIND = "train"


def sizes(cfg):
    return manifest.reference(cfg["name"]).sizes(cfg)


class TrainCell:
    """The compiled step with its state: ONE object, which set-up
    drives through the check steps and then hands to the window."""

    def __init__(self, cfg, mix, chips, seed):
        import paddle_tpu as fluid
        from paddle_tpu.models import transformer

        self.cfg, self.mix, self.chips, self.seed = cfg, mix, chips, seed
        self.sizes = sizes(cfg)
        self.rows = int(mix["rows_per_chip"]) * chips
        self.seq_len = int(mix["seq_len"])
        self.tokens_per_step = self.rows * self.seq_len
        opt = cfg["optimizer"]
        self._flags = None
        if chips > 1:
            self._flags = fluid.get_flags(["FLAGS_sharded_exec",
                                           "FLAGS_sharded_mesh"])
            fluid.set_flags({"FLAGS_sharded_exec": True,
                             "FLAGS_sharded_mesh": str(chips)})
        tcfg = transformer.bert_base(
            vocab_size=self.sizes["vocab_size"],
            d_model=self.sizes["hidden_size"],
            n_heads=self.sizes["num_attention_heads"],
            n_layers=self.sizes["num_hidden_layers"],
            d_ff=self.sizes["intermediate_size"],
            max_seq_len=max(self.seq_len, cfg["max_position_embeddings"]),
            dropout=cfg["hidden_dropout_prob"],
            attn_dropout=cfg["attention_probs_dropout_prob"],
            use_flash="auto")
        main, startup = fluid.Program(), fluid.Program()
        self.scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(self.scope):
            self.loss, _ = transformer.build_train(
                tcfg, self.rows, self.seq_len, lr=opt["lr"], amp=True)
            self.exe = fluid.Executor()
            self.exe.run(startup)
        self.program = main
        if chips > 1:
            self.program = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=self.loss.name)
        self.param_names = [p.name for p in main.all_parameters()]
        self._write_weights()

    def _write_weights(self):
        made = weights.make_weights(self.seed, self.sizes)
        if sorted(made) != sorted(self.param_names):
            raise RuntimeError(
                "the program's parameters are not the leaves the "
                f"benchmark makes: {sorted(set(made) ^ set(self.param_names))[:8]}")
        for name, arr in made.items():
            have = self.scope.find_var(name)
            if tuple(have.shape) != tuple(arr.shape):
                raise RuntimeError(f"{name}: program {have.shape}, "
                                   f"benchmark {arr.shape}")
            self.scope.set(name, arr)

    # -- the window's own call and feed ---------------------------------
    def step(self, batch):
        """Enqueue one step; returns the loss still on the device."""
        x, = self.exe.run(self.program,
                          feed={"tokens": batch, "labels": batch},
                          fetch_list=[self.loss], scope=self.scope,
                          return_numpy=False)
        return x

    # -- what `correct` reads of the program -----------------------------
    def _leaves(self, name_of):
        """{param: the scope's array under name_of(param)}, each leaf
        whole on one device (off the mesh, where there is one)."""
        out = {p: self.scope.find_var(name_of(p)) for p in self.param_names}
        if self.chips > 1:
            out = {p: jnp.asarray(np.asarray(v)) for p, v in out.items()}
        return out

    def grad_norms(self):
        """Per leaf, the norm of the first gradient as the optimizer got
        it: Adam's first moment after one step is (1 - beta1) g."""
        names = self.scope.names()

        def moment1(p):
            acc = [n for n in names if n.startswith(f"{p}_moment1")]
            if len(acc) != 1:
                raise RuntimeError(f"no single moment1 of {p}: {acc}")
            return acc[0]
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        return {k: scale * float(v) for k, v in
                weights.leaf_norms(self._leaves(moment1)).items()}

    def delta_norms(self):
        """Per leaf, the norm of the change against the weights of the
        seed, which are made again rather than kept."""
        change = jax.tree.map(jnp.subtract, self._leaves(lambda p: p),
                              weights.make_weights(self.seed, self.sizes))
        return {k: float(v) for k, v in weights.leaf_norms(change).items()}

    def check_steps(self, batches):
        """The first steps, through `step`: each step's loss, the first
        gradient's norms, the change after the last."""
        losses, grad = [], None
        for i, batch in enumerate(batches):
            losses.append(float(np.asarray(self.step(batch))))
            if i == 0:
                grad = self.grad_norms()
        return {"loss": losses, "grad_norm": grad,
                "delta_norm": self.delta_norms()}

    def executables(self):
        """(name, compiled) of what the window drives, for XLA's memory
        analysis."""
        import paddle_tpu as fluid
        feed = {"tokens": np.zeros((self.rows, self.seq_len), np.int64),
                "labels": np.zeros((self.rows, self.seq_len), np.int64)}
        with fluid.scope_guard(self.scope):
            return [("train_step", self.exe.compiled(
                self.program, feed=feed, fetch_list=[self.loss]))]

    def free(self):
        """Drop the program's state so that the reference fits."""
        import paddle_tpu as fluid
        for n in list(self.scope.names()):
            self.scope.delete(n)
        self.exe.close()
        if self._flags is not None:
            fluid.set_flags(self._flags)


def build(cfg, mix, chips, seed):
    return TrainCell(cfg, mix, chips, seed)


def reference_readings(cfg, seed, batches, **kw):
    return manifest.reference(cfg["name"]).train_readings(
        cfg, seed, batches, **kw)

"""`gpt_serve`: a decoder configuration served by the program's own
path: `serving.GenerationEngine` with paged KV, every engine flag at
its default, `submit` -> `_paged_iteration` (admission, chunked
prefill into the paged pool, decode from it, host sampling).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import manifest, weights

KIND = "serve"


def sizes(cfg):
    return manifest.reference(cfg["name"]).sizes(cfg)


class ServeCell:
    def __init__(self, cfg, mix, chips, seed):
        import paddle_tpu as fluid
        from paddle_tpu.models import gpt
        from paddle_tpu.serving import GenerationEngine

        if chips != 1:
            raise ValueError("gpt_serve runs on one chip")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.sizes = sizes(cfg)
        eng = cfg["engine"]
        self.tcfg = gpt.gpt_small(
            vocab_size=self.sizes["vocab_size"],
            d_model=self.sizes["hidden_size"],
            n_heads=self.sizes["num_attention_heads"],
            n_layers=self.sizes["num_hidden_layers"],
            d_ff=self.sizes["intermediate_size"],
            max_seq_len=cfg["n_positions"], dropout=0.0)
        self.scope = fluid.Scope()
        self.engine = GenerationEngine(
            self.tcfg, self.scope, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"], paged=eng["paged"])
        self.engine.init_scope()
        made = weights.make_weights(seed, self.sizes)
        for name, arr in made.items():
            have = self.scope.find_var(name)
            if have is None or tuple(have.shape) != tuple(arr.shape):
                raise RuntimeError(
                    f"{name}: the engine's scope has "
                    f"{None if have is None else have.shape}, the "
                    f"benchmark makes {arr.shape}")
            self.scope.set(name, arr)
        self.max_slots = eng["max_slots"]
        self.tapped, self.step_log, self.wrapped = {}, None, False
        self._wrap_step_call()

    def warm(self):
        """`start()` warms exactly the engine's own executables."""
        self.engine.start()

    def request(self, prompt, max_new_tokens, on_token, logits=None):
        """Submit one request. `logits`, a list, gets a copy of every
        logits row the engine fetches for this request's sampling."""
        from paddle_tpu.serving import GenerationRequest
        req = GenerationRequest(
            prompt, max_new_tokens,
            temperature=self.mix.get("temperature", 0.0),
            timeout_ms=self.mix["timeout_ms"], stream_cb=on_token)
        if logits is not None:
            self.tapped[id(req)] = (req, logits)
        return self.engine.submit(req)

    def load(self):
        return self.engine.load()

    def _wrap_step_call(self):
        """The benchmark's one tap into the timed path: the engine's
        step call, `_run_paged`, which returns the logits it fetched
        for host sampling. Every decode call hands the rows of the
        tapped requests on (what `correct` compares); under
        `watch_steps` every call is also logged and annotated. A
        program that has no such call any more hands on nothing, and
        `correct` then has no number."""
        inner = getattr(self.engine, "_run_paged", None)
        if inner is None:
            return
        prefill = self.engine._prefill_prog

        def wrapped(prog, step, tokens, table, start, nvalid):
            kind = "prefill" if prog is prefill else "decode"
            log = self.step_log
            if log is None:
                out = inner(prog, step, tokens, table, start, nvalid)
            else:
                with log.annotate(kind):
                    t0 = time.perf_counter()
                    out = inner(prog, step, tokens, table, start, nvalid)
                    t1 = time.perf_counter()
                lt = self.engine.exe.last_step_timings
                log(kind, np.array(start), np.array(nvalid),
                    lt["total_s"] - lt["fetch_s"], t0, t1)
            if kind == "decode" and self.tapped:
                for i in np.flatnonzero(nvalid):
                    st = self.engine._state[i]
                    hit = self.tapped.get(id(st.req)) if st else None
                    if hit is not None:
                        hit[1].append(np.array(out[i, 0], np.float32))
            return out
        self.engine._run_paged = wrapped
        self.wrapped = True

    def watch_steps(self, on_call):
        """Counters of the traced run: every executable run reports
        (kind, rows' start positions, rows' valid tokens, host seconds,
        t0, t1)."""
        self.step_log = on_call
        return self.wrapped

    def post_warmup_compiles(self):
        return self.engine.post_warmup_compiles()

    def stop(self):
        self.engine.stop(drain=False, timeout=60.0)

    def executables(self):
        import paddle_tpu as fluid
        out = []
        with fluid.scope_guard(self.scope):
            for name, prog, feed, fetch in self.engine.executables():
                out.append((name, self.engine.exe.compiled(
                    prog, feed=feed, fetch_list=[fetch])))
        return out

    def free(self):
        for n in list(self.scope.names()):
            self.scope.delete(n)
        self.engine.exe.close()


def build(cfg, mix, chips, seed):
    return ServeCell(cfg, mix, chips, seed)

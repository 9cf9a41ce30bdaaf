"""Driver of the federated LoRA stage: ``BatchedLLMEngine.run``.

Set-up makes the federation, the frozen base and the clients' initial
adapters from the seed, builds one engine (its clients sharded over the
``'clients'`` mesh where the cell has several chips) and drives its
first call, which compiles.  Each timed call is one more ``run()`` of
the same engine: ``steps_per_call`` LoRA steps of every client, the
FedAvg teacher and distillation blend, the label-head evaluation, and
the transfer of losses, F1 and soft labels to the host.  The work of a
call is its trained tokens: clients x batch x seq_len x steps.

The check replays the first call with the plain reference
(``ref_llm.stage``) on the same base, initial adapters and shards.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import numpy as np

from benchmarks.chip import compare, gen, llm, ref_llm


class Driver:
    def __init__(self, config: dict, params: dict, seed: int, devices):
        from repro.core.batched_llm import BatchedLLMEngine

        t0 = time.perf_counter()
        self.d = llm.dims(config)
        self.p = dict(params)
        self.lr = float(config["llm_lr"])
        self.n_chips = len(devices)
        self.fed = gen.federation(params, seed, self.d["vocab_size"])
        self.seed = gen.sub_seed(seed, 3)
        base = llm.make_base(self.d, gen.sub_seed(seed, 2))
        self.engine = BatchedLLMEngine(
            self.fed, llm.program_config(config), base, seed=self.seed,
            lr=self.lr, steps=int(params["steps_per_call"]),
            batch_size=int(params["batch"]), rho=float(params["rho"]),
            n_devices=self.n_chips if self.n_chips > 1 else None)
        del base                      # the engine holds it (replicated)
        _log(f"base and engine built in {time.perf_counter() - t0:.3f} s")
        c_pad = jax.tree.leaves(self.engine.adapters)[0].shape[0]
        a0 = llm.make_adapters(self.d, c_pad, gen.sub_seed(seed, 4))
        self.a0 = jax.device_get(a0)
        if self.n_chips > 1:
            # placed as the engine placed its own; on one chip they stay
            # uncommitted like the engine's, or the steady-state call
            # (committed outputs) would compile a second program
            a0 = jax.tree.map(lambda new, old: jax.device_put(new, old.sharding),
                              a0, self.engine.adapters)
        self.engine.adapters = a0
        del a0
        t1 = time.perf_counter()
        out = self.engine.run()
        _log(f"first call (compiles or loads) {time.perf_counter() - t1:.3f}"
             f" s; eval loss {out.losses.tolist()} F1 {out.f1.tolist()}")
        self.first = {
            "adapters": compare.per_client_leaves(
                jax.device_get(self.engine.adapters)),
            "moments": compare.per_client_leaves(
                jax.device_get(self.engine.opt_state.mu)),
            "train_loss": out.final_train_loss, "eval_loss": out.losses,
            "f1": out.f1, "teacher": out.teacher}
        if self.n_chips > 1:
            # on the mesh the first call's outputs come back with other
            # shardings than the placed inputs, so the steady-state call
            # is a second program: compile it here, not in the window
            t1 = time.perf_counter()
            self.engine.run()
            _log(f"second call (steady-state program) "
                 f"{time.perf_counter() - t1:.3f} s")
        C = self.fed.n_clients
        self.tokens_per_call = (C * int(params["batch"])
                                * int(params["seq_len"])
                                * int(params["steps_per_call"]))
        self.calls = 0
        self.base = None

    def call(self) -> float:
        with jax.profiler.TraceAnnotation("bench.llm_stage.run"):
            self.engine.run()
        self.calls += 1
        return self.tokens_per_call

    def counts(self) -> dict:
        C, p = self.fed.n_clients, self.p
        return {"dims": self.d, "clients": C, "batch": int(p["batch"]),
                "seq": int(p["seq_len"]), "steps": int(p["steps_per_call"]),
                "eval_rows": sum(cl.n for cl in self.fed.clients),
                "calls": self.calls}

    def release(self) -> None:
        """Frees the engine; keeps one device's copy of the base, which the
        benchmark made, for the reference."""
        self.base = jax.tree.map(
            lambda x: x.addressable_shards[0].data, self.engine._base)
        self.engine = None
        gc.collect()

    def reference(self, precision=jax.lax.Precision.HIGHEST,
                  fault: str = "") -> dict:
        """The plain reference's first call, in the form ``first`` has."""
        C = self.fed.n_clients
        t0 = time.perf_counter()
        ref = ref_llm.stage(
            self.d, self.base, jax.tree.map(lambda x: x[:C], self.a0),
            [(cl.llm_batch["tokens"], cl.llm_batch["labels"])
             for cl in self.fed.clients], self.fed.weights,
            seed=self.seed, steps=int(self.p["steps_per_call"]),
            batch=int(self.p["batch"]), lr=self.lr,
            rho=float(self.p["rho"]), n_chips=self.n_chips,
            precision=precision, fault=fault)
        _log(f"reference ({precision}, fault {fault or 'none'}) "
             f"{time.perf_counter() - t0:.3f} s")
        ref["adapters"] = [_flat(a) for a in ref["adapters"]]
        ref["moments"] = [_flat(m) for m in ref["moments"]]
        return ref

    def numbers(self, prog: dict, ref: dict) -> dict:
        C = self.fed.n_clients
        prog = dict(prog, adapters=prog["adapters"][:C],
                    moments=prog["moments"][:C])
        a0 = compare.per_client_leaves(
            jax.tree.map(lambda x: x[:C], self.a0))
        return compare.llm_stage(prog, ref, a0)

    def verify(self) -> dict:
        return self.numbers(self.first, self.reference())


def _log(msg: str) -> None:
    print(f"llm_stage: {msg}", file=sys.stderr, flush=True)


def _flat(tree) -> dict:
    return compare.per_client_leaves(
        jax.tree.map(lambda x: np.asarray(x)[None], tree))[0]


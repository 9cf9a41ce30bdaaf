"""Names of the device scopes and host spans the programs write.

Device scopes (``jax.named_scope``) mark the layer boundaries of the two
jitted programs, the batched LLM stage (``core/batched_llm.py``) and the
fused rounds (``core/fused_rounds.py``).  They change metadata only:
each lands in the optimized HLO's ``op_name`` of the instructions it
covers, through ``vmap``, ``scan``, ``grad`` and ``jax.checkpoint``
(backward ops read ``transpose(jvp(<scope>))``, remat's recompute
``checkpoint/rematted_computation/<scope>``; a fusion carries its root's
name).  A profiler's device ops name only the instruction, so a scope is
looked up by instruction name in the program's compiled text, which
``BatchedLLMEngine.compiled_text`` and ``FusedRoundDriver.compiled_text``
return.

Host spans (``jax.profiler.TraceAnnotation``, free while no profiler
records) split each ``run()`` of the two engines into its host phases;
each call sits under a ``jax.profiler.StepTraceAnnotation`` of its own
step number, so the spans of one call share it.
"""
from __future__ import annotations

# device scopes: the LLM stage
LLM_SAMPLE = "llm.sample"        # minibatch keys, draw and gather
LLM_STEP = "llm.step"            # the vmapped LoRA train step
LLM_ADAMW = "llm.adamw"          # optim/adamw.update
LLM_FEDAVG = "llm.fedavg"        # FedAvg teacher and distillation blend
LLM_EVAL = "llm.eval"            # the vmapped label-head evaluations
MODEL_HEAD = "model.head"        # the vocabulary projection (and its CE)

# device scopes: the fused rounds
QFL_GATHER = "qfl.gather"        # cohort, dropout and the cohort's rows
QFL_REGULATE = "qfl.regulate"
QFL_LOCAL = "qfl.local"          # the local phase (batched optimizer)
QFL_REPORT = "qfl.report"        # the clients' loss reports
QFL_SERVER = "qfl.server"        # the server's NLL and accuracy evals
QFL_SELECT = "qfl.select"
QFL_FEDAVG = "qfl.fedavg"
QFL_TERMINATE = "qfl.terminate"
QFL_SCATTER = "qfl.scatter"      # the cohort's state back to the carries
NM_INIT = "nm.init"              # initial simplexes and their evaluations
NM_ITERATE = "nm.iterate"        # the lockstep loop
TAPE_REPLAY = "tape.replay"      # quantum/tape.tape_probs

LLM_SCOPES = (LLM_SAMPLE, LLM_STEP, LLM_ADAMW, LLM_FEDAVG, LLM_EVAL,
              MODEL_HEAD)
ROUND_SCOPES = (QFL_GATHER, QFL_REGULATE, QFL_LOCAL, QFL_REPORT,
                QFL_SERVER, QFL_SELECT, QFL_FEDAVG, QFL_TERMINATE,
                QFL_SCATTER, NM_INIT, NM_ITERATE, TAPE_REPLAY)

# host spans: BatchedLLMEngine.run
LLM_STAGE = "llm.stage"                      # the step span of a call
LLM_STAGE_DISPATCH = "llm.stage.dispatch"    # the jitted call (enqueue)
LLM_STAGE_FETCH = "llm.stage.fetch"          # device-to-host transfers

# host spans: FusedRoundDriver.run
QFL_ROUNDS = "qfl.rounds"                    # the step span of a call
QFL_ROUNDS_ARGS = "qfl.rounds.args"          # program_args, θ's put
QFL_ROUNDS_DISPATCH = "qfl.rounds.dispatch"
QFL_ROUNDS_FETCH = "qfl.rounds.fetch"        # device_get
QFL_ROUNDS_UNPACK = "qfl.rounds.unpack"      # FusedRunOutput


def nbytes(tree) -> int:
    """Bytes of a pytree's array leaves, read from their shapes (no
    transfer, no sync): the metadata of a fetch span."""
    import jax
    return int(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree)))

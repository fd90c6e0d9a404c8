"""Named host spans of a training step (``dynapipe.*`` profiler events).

Each span is a ``jax.profiler.TraceAnnotation``: when a profiler runs it
lands in the same trace as the device's ops, on one clock, with its keyword
args as event stats; when none runs it costs about half a microsecond.
There is no switch. Args are host values already in hand: no span reads a
device value, so none adds a sync.

Main (runner) thread, inside one ``dynapipe.iteration`` per loop pass:
``submit``, ``plan_wait``, ``materialize``, ``stage_setup`` (with a
``compile`` per stage program compiled there, with args ``stage``,
``kind`` (``fwd``, ``bwd`` or the last stage's ``fwd_bwd``), ``shape`` and
``remat``, 1 where the program checkpoints each period), ``pipeline``,
``grad_merge`` (which first waits for the last stage's last program, so
that the merge's output is not allocated beside it), ``optimizer``
(dispatch), ``step_sync`` and ``loss_sync``, the step's one read of its
losses, with arg ``n_reads`` (the device scalars read); ``iteration`` also
carries ``pipeline_syncs``, the waits on the
device made to time the pipeline (one per timed callback when the
calibrator collects timings, one per replica when a monitor times
replicas). ``pipeline`` is ``PipelineExecutor.run``, or the sequential
path's micro-batch loop (with its own ``device_put`` and a ``loss_sync`` per
micro-batch). Stage compute threads: ``stage{j}.fwd`` / ``stage{j}.bwd``
(with ``device_put`` in stage 0's forward) and ``recv_wait``; none reads
the device. Planner threads: ``plan``. An encoder-decoder plan's ``plan``
and ``iteration`` also carry ``real_enc_tokens``, ``padded_enc_tokens``,
``real_dec_tokens`` and ``padded_dec_tokens`` (``encdec_tokens``).

Device programs: the stage programs ``jit_stage{j}_fwd`` / ``_bwd`` and
the last stage's ``jit_stage{c-1}_fwd_bwd``, AdamW's ``jit_adamw_step``.
Within them, the Pallas attention kernels with T5's relative position bias
are named ``flash_fwd_relbias``, ``flash_dq_relbias`` and
``flash_dkv_relbias``; the others keep their kernels' function names.
"""
from __future__ import annotations

ITERATION = "dynapipe.iteration"
SUBMIT = "dynapipe.submit"
PLAN_WAIT = "dynapipe.plan_wait"
MATERIALIZE = "dynapipe.materialize"
STAGE_SETUP = "dynapipe.stage_setup"
COMPILE = "dynapipe.compile"
PIPELINE = "dynapipe.pipeline"
GRAD_MERGE = "dynapipe.grad_merge"
OPTIMIZER = "dynapipe.optimizer"
STEP_SYNC = "dynapipe.step_sync"
DEVICE_PUT = "dynapipe.device_put"
LOSS_SYNC = "dynapipe.loss_sync"
RECV_WAIT = "dynapipe.recv_wait"
PLAN = "dynapipe.plan"

_annotation = None


def encdec_tokens(lengths, micro_batches) -> dict:
    """Real and padded tokens per side of an encoder-decoder plan, as span
    args; {} when the micro-batches are decoder-only (an int ``seq``).
    ``lengths`` is (n, 2): encoder, decoder."""
    sides = [m for m in micro_batches if isinstance(m.seq, (tuple, list))]
    if not sides:
        return {}
    enc = dec = 0
    for n_enc, n_dec in lengths:
        enc, dec = enc + int(n_enc), dec + int(n_dec)
    return {"real_enc_tokens": enc,
            "padded_enc_tokens": sum(m.mbs * int(m.seq[0]) for m in sides),
            "real_dec_tokens": dec,
            "padded_dec_tokens": sum(m.mbs * int(m.seq[1]) for m in sides)}


def stage(j: int, kind: str) -> str:
    """``dynapipe.stage{j}.fwd`` or ``.bwd``."""
    return f"dynapipe.stage{j}.{kind}"


def span(name: str, **args):
    """A context manager for the span ``name``; ``set_metadata(**args)`` on
    it adds args known only once the span is open. jax is imported on the
    first call, so importing this module (and ``repro.core``) stays free of
    it."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **args)

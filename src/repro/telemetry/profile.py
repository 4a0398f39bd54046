"""jax.profiler hooks: Perfetto-loadable traces of train and serve.

``trace_session(dir)`` wraps a whole run in ``jax.profiler.start_trace``
/ ``stop_trace`` (a no-op context when ``dir`` is falsy — the
``--profile-dir`` gate in both launchers).  ``annotate``/``step_annotation``
mark HOST-side regions (round dispatches, serve calls) on the trace
timeline; regions inside a compiled program are labelled with
``jax.named_scope`` at their definition sites instead, since host
annotations cannot see inside it. The names on a trace:

    host spans                      where
    repro/round (step number)       experiment loop engine, per round
    serve/call (call, batch)        ClusterPlaneServer entry points
      serve/dispatch, serve/block   the jitted call; the device wait
    named scopes (a device op's tf_op path)
    fedspd/select                   round step: cluster draw, row select
    fedspd/cluster                  batch assignment and u, re-clustering
    fedspd/local_step               unpack, tau SGD steps, pack
    fedspd/scatter                  mixed rows written into the plane
    fedspd/consensus                the per-cluster consensus metric
    comm/encode                     the codec's encode
    attn                            reference and blocked attention
    gossip/mix_dense, gossip/mix_permute, serve/mix_<codec>, serve/unpack

Open the result at https://ui.perfetto.dev (or
``tensorboard --logdir <dir>``): the ``.trace.json.gz`` under
``<dir>/plugins/profile/<run>/`` loads directly.

Everything degrades to a no-op when the profiler API is unavailable —
telemetry must never fail a run.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def trace_session(profile_dir=None):
    """Profile the enclosed block into ``profile_dir`` (no-op when None)."""
    if not profile_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(str(profile_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **stats):
    """Named host-span context (TraceAnnotation); no-op off-trace.
    ``stats`` (numbers or strings) ride the span as its trace stats."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **stats)
    except Exception:
        return contextlib.nullcontext()


def step_annotation(name: str, step: int):
    """Host-span carrying a step number (StepTraceAnnotation) — the
    profiler's per-step lane groups round dispatches by it."""
    try:
        import jax

        return jax.profiler.StepTraceAnnotation(name, step_num=int(step))
    except Exception:
        return contextlib.nullcontext()

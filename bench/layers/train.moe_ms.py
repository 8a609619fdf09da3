"""The MoE layer in the train step (``models/moe``, forward and backward:
router, dispatch, the experts' dequant, grouped matmuls and adapters,
combine), per traced step, in ms: device time of the ops under the
``moe`` scope plus that of the grouped matmuls (the ``ragged-dot`` ops)
that do not carry the scope, which the TPU compiler's kernel for
``jax.lax.ragged_dot`` leaves without it: each op is counted once.
Moves ``train_tokens_per_s``.  Nothing to read where no op carries the
scope."""
from bench import program_trace, trace_reduce, xplane
from bench.jobs.train import TRACE_STEPS

SCOPE = "moe"
KERNEL = "ragged-dot"


def unscoped_kernel_seconds(planes) -> float:
    """Device seconds per chip of the ops whose own name holds ``KERNEL``
    and whose name stack does not hold ``SCOPE``; ``planes`` as
    :func:`bench.xplane.read` gives them."""
    secs, n = 0.0, 0
    for plane in planes:
        n += 1
        for ln in plane.lines:
            for ev in ln.events:
                mid = ev.metadata_id
                own = plane.names.get(mid, "").split(" = ", 1)[0]
                tf_op = plane.stats.get(mid, {}).get(xplane.TF_OP, "")
                if KERNEL in own and SCOPE not in xplane.components(
                        tf_op)[:-1]:
                    secs += ev.duration_ns * 1e-9
    return secs / n if n else 0.0


def _planes() -> list:
    from bench import harness
    return xplane.read(trace_reduce.trace_file(harness.TRACE_DIR),
                       trace_reduce.DEVICE_PREFIX, trace_reduce.OPS_LINE)


def read(run):
    if run["job"] != "train":
        return None
    tr = program_trace.current()
    secs = tr.scope_seconds.get(SCOPE, 0.0) if tr else 0.0
    if secs <= 0:
        return None
    return 1e3 * (secs + unscoped_kernel_seconds(_planes())) / TRACE_STEPS

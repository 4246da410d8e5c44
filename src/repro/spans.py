"""The names under which the replay engine's layers appear in a profiler
trace: the one place they are spelled.

Device scopes (``jax.named_scope``) mark the work of one layer inside a
jitted program.  They change HLO metadata only: each operation's
``op_name`` gains the scope as one ``/``-separated element, and the
compiled code is the same.  Host spans (``jax.profiler.TraceAnnotation``)
mark what the host does for the streaming engines, on the device
trace's clock.  With no profiler running both cost next to nothing.
A device operation belongs to the innermost ``repro.`` element of its
``op_name``, which the profiler's trace keeps in the ``tf_op`` stat of
the operation's event metadata (a fusion carries its root's).
"""

# device scopes, one per layer of the fused slab step
WORKLOAD_GEN = "repro.workload_gen"  # counter draws, chains, hold-resample
VALUE_LOWERING = "repro.value_lowering"  # pool gathers, gain, quantization
ROLLOUT = "repro.rollout"  # the Pallas rollout kernel and its operands
ADMISSION = "repro.admission"  # per-slot cloudlet capacity admission
ACCOUNTING = "repro.accounting"  # per-slot series and their buffers

SCOPES = (WORKLOAD_GEN, VALUE_LOWERING, ROLLOUT, ADMISSION, ACCOUNTING)

# host spans of the streaming walks (simulate_chunked_stream,
# simulate_sharded_stream)
STREAM_PREPARE = "repro.stream.prepare"  # checks, state copies, buffers
STREAM_DISPATCH = "repro.stream.dispatch"  # one slab's launch; arg t0
STREAM_TAIL = "repro.stream.tail"  # the sub-chunk tail's jnp steps

STREAM_SPANS = (STREAM_PREPARE, STREAM_DISPATCH, STREAM_TAIL)

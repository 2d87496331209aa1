"""What a mover-jax client and server both say on the wire: the
service's name and its metadata keys. A leaf: it imports nothing, so a
mover on a CPU node learns the name of a header without loading the
batcher, the scheduler or a device program (``service/client.py`` and
``service/server.py`` both import it; ``tests/test_layering.py`` holds
the client to it)."""

SERVICE_NAME = "moverjax.MoverJax"
TOKEN_METADATA_KEY = "x-volsync-token"
#: trailing-metadata key carrying the shed retry-after hint (ms)
RETRY_AFTER_METADATA_KEY = "x-volsync-retry-after-ms"
#: trailing-metadata key carrying a sibling replica's host:port on a
#: shed, when a fleet router is wired (cross-replica admission: retry
#: THERE, not here)
SIBLING_METADATA_KEY = "x-volsync-sibling"
#: request-metadata key carrying the client's trace context
#: (obs.format_trace_header) so client + server spans join one trace
TRACE_METADATA_KEY = "x-volsync-trace"
#: request-metadata key naming the stream's deadline class
#: (scheduler.parse_deadline_classes); unknown/absent = no deadline
DEADLINE_CLASS_METADATA_KEY = "x-volsync-deadline-class"

"""Low-level ops: payload packing, segment primitives, and the Pallas
kernels for the dispatch hot path — mailbox_kernel (drain) and
fused_dispatch (drain+behaviour+outbox)."""

# Pallas TPU kernels of the serving hot path (paged_attention, flash_prefill)
# plus rwkv6_chunk; ref.py holds the pure-jnp oracle of each. Every kernel
# takes a required ``interpret`` flag: False compiles the Mosaic kernel,
# True runs its body in the Pallas interpreter (CPU validation).

"""How the program's device work is named in the trace.

The program gives its kernels and steps no names of their own yet
(``pallas_call`` has no ``name=``), so these match what JAX derives from the
Python functions: on a v5e the attention kernel's operation is named after
its jitted wrapper (``paged_prefill_attention_pallas.<n>``), the mixing
kernel's after ``graph_mix_pallas``, and the step program after
``prefill_chunk_parallel``.
"""


def is_step(name: str) -> bool:
    return "prefill_chunk" in name


def is_attention(name: str) -> bool:
    return "attention_pallas" in name


def is_graph_mix(name: str) -> bool:
    return "graph_mix" in name

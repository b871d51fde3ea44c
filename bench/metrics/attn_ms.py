"""Device time of the attention core (QK, softmax, PV: the model's
``attention`` scope) per paged decode program run, from the ops' scopes in
the trace (``bench/program_trace.py``).  Moves time per output token."""

from bench import program_trace

program_trace.attach()


def read(run):
    return program_trace.scope_ms_per_run(run, "_decode_paged_fn",
                                          "attention")

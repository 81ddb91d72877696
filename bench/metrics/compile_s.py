"""compile_s: seconds JAX spent tracing, lowering and compiling (cache reads
included) during set-up, from its monitoring events. Moves setup_s."""


def read(record):
    return record["compile_s"]

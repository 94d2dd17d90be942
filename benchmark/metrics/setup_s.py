"""setup_s: seconds from process start to the first admitted document
(host clock): JAX and chip start-up, the first shards, the pipeline's build
and warmup (executable-store loads or compiles), the host executors."""


def read(record):
    return record["setup_s"]

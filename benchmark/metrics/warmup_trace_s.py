"""warmup_trace_s: serial trace-and-lower seconds of the pipeline's warmup
(``WarmupStats.trace_s``); 0 when every program came from the executable
store."""


def read(record):
    w = record.get("warmup")
    return None if w is None else w["trace_s"]

"""Seconds the process spent tracing, lowering and compiling (or loading
from the compile cache) before the window opened: what set-up pays for
its programs. Source: the program's own process-wide compile log
(``deeplearning4j_tpu.obs.compile_log``), installed when this reader is
loaded, before set-up starts, so the weights' and the correctness check's
compiles are counted too; nested stages are counted once. A program
without that log reports nothing."""

try:
    from deeplearning4j_tpu.obs import compile_log
except ImportError:  # a program older than its compile log
    _LOG = None
else:
    _LOG = compile_log.install()


def snapshot(system):
    return _LOG.snapshot() if _LOG is not None else None


def read(m):
    return m.before[1] if m.before is not None else None

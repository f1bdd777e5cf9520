"""Compile requests the process made before the window opened: every
program set-up had to build or load from the compile cache (a cache hit
is a request). Source: the program's own process-wide compile log
(``deeplearning4j_tpu.obs.compile_log``), installed when this reader is
loaded, before set-up starts. A program without that log reports
nothing."""

try:
    from deeplearning4j_tpu.obs import compile_log
except ImportError:  # a program older than its compile log
    _LOG = None
else:
    _LOG = compile_log.install()


def snapshot(system):
    return _LOG.snapshot() if _LOG is not None else None


def read(m):
    return float(m.before[0]) if m.before is not None else None

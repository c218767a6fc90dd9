"""Device milliseconds per execution of the decode-step program
(``jit_serve_step``, the program's name for ``make_serve_step``)."""


def read(v):
    n, seconds = v.trace.module_runs("jit_serve_step")
    return 1e3 * seconds / n if n else None

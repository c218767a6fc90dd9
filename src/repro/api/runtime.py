"""Scoped runtime configuration — ``repro.api.config``.

``config`` overrides the kernels' impl dispatch
(``auto``/``pallas``/``reference``) through a ContextVar, so the override
is visible exactly within the ``with`` block and the current thread/task:
a parallel benchmark keeps its own view, and nothing leaks across test
boundaries.

    with repro.api.config(impl="reference"):
        y = repro.api.kernel("expf").run(x)

The import of the kernel stack (and therefore jax) is deferred to the
first use, so ``import repro.api`` stays cheap for model-only consumers.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def config(impl: str | None = None):
    """Scoped kernel-runtime override: ``impl`` is the 'auto' | 'pallas' |
    'reference' kernel dispatch.

    ``None`` leaves the setting untouched.  It is restored on exit even on
    error; nesting composes (inner scopes win).
    """
    from repro.kernels import ops as kops
    with kops.overrides(impl=impl):
        yield

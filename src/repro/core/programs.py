"""Compiled programs, built once per process and key.

Every driver turns its bodies into compiled programs here. A program is
keyed by its body (a module-level function), the body's static keyword
arguments, the backend, the marks and donated positions, each input's
shape, dtype, weak type and sharding, and the kernel settings read while
tracing (``ops._backend(None)``, ``tuning.autotune_mode()``). On a miss
``build`` compiles the body through ``backend.compile``, lowers and
compiles it for the given inputs under a wire tally, and keeps the
executable with that tally. On a hit it returns the kept program.

So a traced and an untraced fit run the same executable, and a fit whose
programs an earlier fit built traces nothing. Where part of the key
cannot be hashed, the program is built for that call and not kept.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import WireTally, wire_tally
from repro.kernels import ops
from repro.kernels.tuning import autotune_mode
from repro.obs import trace as obs_trace

# Programs kept per process, least recently used out first: eight fits'
# worth of SOCCER's three programs.
CACHE_SIZE = 24
_PROGRAMS: "collections.OrderedDict[tuple, Program]" = (
    collections.OrderedDict())


@dataclasses.dataclass(frozen=True, eq=False)
class Program:
    """One compiled executable and the wire bytes its lowering recorded."""
    executable: Any
    tally: WireTally

    def __call__(self, *args):
        return self.executable(*args)


def clear_program_cache() -> None:
    """Forget every kept program: the next fit builds its programs anew."""
    _PROGRAMS.clear()


def _aval(a) -> tuple:
    if isinstance(a, jax.Array):        # the attributes, not the slower
        return a.shape, a.dtype, a.weak_type, a.sharding   # jnp helpers
    return jnp.shape(a), jnp.result_type(a)


def _key(body, static: dict, backend, in_marks, out_marks, donate,
         inputs: tuple) -> tuple:
    leaves, tree = jax.tree.flatten(inputs)
    return (body, tuple(sorted(static.items())), backend, in_marks,
            out_marks, donate, tree, tuple(_aval(a) for a in leaves),
            ops._backend(None), autotune_mode())


def build(scope: str, name: str, body, backend, in_marks: tuple, out_marks,
          inputs: tuple, *, static: Optional[dict] = None,
          donate: Tuple[int, ...] = ()) -> Tuple[Program, Optional[float]]:
    """The program that runs ``body(*inputs, **static)`` under ``backend``.

    Returns ``(program, compile_s)``. ``compile_s`` is 0.0 where an earlier
    call built the program. Where this call builds it, the build runs in a
    ``<scope>.compile`` span with attr ``program=name``, and ``compile_s``
    is that span's interval in a traced fit, None in an untraced one. The
    active trace counts ``programs_traced`` and ``program_cache_hits``.
    """
    static = static or {}
    key = _key(body, static, backend, in_marks, out_marks, donate, inputs)
    try:
        prog = _PROGRAMS.get(key)
    except TypeError:
        key = prog = None
    if prog is not None:
        _PROGRAMS.move_to_end(key)
        obs_trace.count("programs_traced", 0)
        obs_trace.count("program_cache_hits")
        return prog, 0.0
    tally = WireTally()
    with obs_trace.span(f"{scope}.compile", program=name) as sp:
        fn = backend.compile(functools.partial(body, **static), in_marks,
                             out_marks, donate)
        with wire_tally(tally):
            prog = Program(fn.lower(*inputs).compile(), tally)
    obs_trace.count("programs_traced")
    obs_trace.count("program_cache_hits", 0)
    if key is not None:
        _PROGRAMS[key] = prog
        if len(_PROGRAMS) > CACHE_SIZE:
            _PROGRAMS.popitem(last=False)
    return prog, (sp.t1 - sp.t0 if obs_trace.current_trace() else None)

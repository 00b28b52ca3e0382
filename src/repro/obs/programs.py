"""Names of the runtime's jitted programs.

A jitted function's program is named after the Python function
(``jit__search``), and that name is what a device trace shows: readers of
the trace find the runtime's programs by it.  The runtime builds every
step through :func:`jit_program`, which names the program from this
catalog whatever the Python closure is called, so renaming a closure
cannot silently rename a program.
"""

from __future__ import annotations

PROGRAM_SEARCH = "_search"
PROGRAM_INSERT = "_insert"
PROGRAM_DELETE = "_delete"
PROGRAM_UPDATE = "_update"
PROGRAM_FUSED = "_fused"  # one search batch + one mutation run

PROGRAMS = frozenset({
    PROGRAM_SEARCH,
    PROGRAM_INSERT,
    PROGRAM_DELETE,
    PROGRAM_UPDATE,
    PROGRAM_FUSED,
})


def module_name(program: str) -> str:
    """The program's name in a device trace (the HLO module name)."""
    return f"jit_{program}"


def jit_program(program: str, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the catalog name ``program``."""
    import jax

    if program not in PROGRAMS:
        raise ValueError(
            f"unregistered program {program!r}; known programs: "
            f"{sorted(PROGRAMS)} (register in repro.obs.programs)"
        )
    fn.__name__ = fn.__qualname__ = program
    return jax.jit(fn, **jit_kwargs)

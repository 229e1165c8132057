"""compiles_in_window — compile: jit compiles counted by compile_watch inside the window.
Reported as compiles_in_window.sat and compiles_in_window.tail."""

from benchlib import observe


def read(obs):
    return observe.compiles_in_window(obs)

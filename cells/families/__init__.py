"""Model families, found by the name a configuration gives as ``family``.

``cells/families/<name>.py`` is the only place in ``cells/`` that imports a
model of the program (contract: ``cells/README.md``, "A model family").
The driving process loads a family for its arithmetic and never touches
JAX, so a family file imports the program, ``jax`` and its reference
inside the functions that need them.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"cells.families.{name}")

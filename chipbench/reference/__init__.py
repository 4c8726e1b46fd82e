"""Plain references, one module per architecture, written from the
published descriptions and sharing no code with the system under test.
A configuration file names its architecture under ``reference``: the same
name finds the reference here, ``reference/<name>.py``, and what the
benchmark knows of the architecture, ``arch/<name>.py``."""

from types import ModuleType

from chipbench.spec import ROOT, named_module


def module(name: str) -> ModuleType:
    return named_module(ROOT, "reference", name)

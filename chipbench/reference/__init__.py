"""Plain references, one module per architecture, written from the
published descriptions and sharing no code with the system under test.
A configuration file names its module under ``reference``."""

import importlib


def module(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")

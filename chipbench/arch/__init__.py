"""What the benchmark knows of one architecture, one module per
architecture.  A configuration file names its architecture under
``reference``: the same name finds this module, ``arch/<name>.py``, and
the plain reference, ``reference/<name>.py``.  A later architecture is
added as those two new files.

Each module gives, with ``m`` a configuration file's ``model``:

  spec(m)                  the tensors by published name, as
                           ((name, shape, std, mean), ...), which
                           ``weights.generate`` makes from the seed
  model_config(config)     the program's ``ModelConfig`` for the file, or
                           ValueError where the program cannot run it
  program_params(w, cfg)   the program's parameter tree holding ``w``;
                           ``program.params`` checks it against the
                           program's own tree
  parameter_count(m)       the number of parameters
  step_work(m, contexts)   (FLOPs, bytes) of one decode step over tokens
                           at ``contexts``
  session_bytes(m, n)      bytes of one session's live state after ``n``
                           positions
"""

from types import ModuleType

from chipbench.spec import ROOT, named_module


def module(name: str) -> ModuleType:
    return named_module(ROOT, "arch", name)

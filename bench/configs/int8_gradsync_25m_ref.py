"""Plain reference of ``int8_gradsync_25m``: the same error-fed lossy
mean as ``int8_gradsync``, whose reference this loads by path (its
``numbers`` and ``control``) rather than copying it.  It imports
nothing of the program."""

import importlib.util
from pathlib import Path

_path = Path(__file__).with_name("int8_gradsync_ref.py")
_spec = importlib.util.spec_from_file_location("int8_gradsync_ref", _path)
_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ref)

numbers = _ref.numbers
control = _ref.control

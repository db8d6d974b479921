"""Loads the code a configuration brings with it (contract: the docstring
of perfbench/run.py). A configuration's JSON names each file; the file
lives in the folder of its kind under perfbench/ and is found by that
name alone, never by the name of a model."""

from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\.py$")
_loaded: dict = {}


def load(folder: str, file: str):
    """The module perfbench/<folder>/<file>, executed once a process."""
    if not FILE.match(file):
        raise ValueError(f"{file!r} is not the bare name of a .py file")
    path = os.path.join(HERE, folder, file)
    if path not in _loaded:
        if not os.path.exists(path):
            raise FileNotFoundError(f"no file perfbench/{folder}/{file}")
        name = f"perfbench_{folder}_{file[:-3]}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _loaded[path] = module
    return _loaded[path]

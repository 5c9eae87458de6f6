"""The harness's parts that are found by name in its data directory.

- ``families/<name>.py``: a denoiser family, named by a configuration
  file's ``family`` key (see ``families/dit.py`` for what one gives);
- ``schedules/<kind>.py``: a noise schedule, named by the configuration
  file's ``schedule.kind``;
- ``metrics/<name>.py``: a per-layer metric's reader, named by
  ``BENCHMARK.json``.

Each is a plain Python file, so a later cell adds one and edits none.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import os
import sys


@functools.cache
def load(data_dir: str, kind: str, name: str):
    """The module ``<data_dir>/<kind>/<name>.py``, loaded once a process."""
    path = os.path.join(data_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} module {name!r}: {path} is not there")
    # registered under a name of its own path, as an imported module is
    # (dataclasses and pickle look a class's module up by name)
    key = hashlib.sha256(os.path.abspath(path).encode()).hexdigest()[:12]
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}_{key}",
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod

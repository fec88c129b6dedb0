"""A deployment, read from its configuration file by the family the file
names.

JAX-free: the generators, the probe and the reference read the same object
that ``cellbench/server.py`` loads into the service. What a rule, a row and a
frame are belongs to the deployment's *family* (``cellbench/families/``):
the file's ``"family"`` key names it, and a file without the key is a flow
table (``families/flow.py``). This module holds what every family shares: the
token server's statuses and the way from a file to its family.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# statuses (TokenResultStatus of the reference, plus this server's own)
OK, BLOCKED, SHOULD_WAIT, NO_RULE, TOO_MANY, FAIL = 0, 1, 2, 3, 4, 5
OVERLOAD, STANDBY, MOVED, DEGRADED = 8, 9, 10, 12
# a verdict the device decided; everything else is a failure (ISSUE A.3)
DECISIONS = (OK, BLOCKED, SHOULD_WAIT, NO_RULE, TOO_MANY, DEGRADED)
DECIDED = np.zeros(256, bool)  # the same, as a table over status bytes
DECIDED[list(DECISIONS)] = True
# control behaviours of a rule (RuleConstant of the reference)
DEFAULT, WARM_UP, RATE_LIMITER, WARM_UP_RATE_LIMITER = 0, 1, 2, 3


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def family(name: str, dirs=()):
    """The module ``<a directory>/families/<name>.py``, looked for in
    ``dirs`` (the directories of ``paths``) and then in ``cellbench/``
    itself. A family of ``cellbench/`` is the package's own module; one from
    elsewhere is loaded by its path, once, with its directory importable so
    that it can import helpers that sit beside it."""
    for d in list(dirs) + [HERE]:
        path = os.path.abspath(os.path.join(d, "families", name + ".py"))
        if not os.path.exists(path):
            continue
        if os.path.dirname(os.path.dirname(path)) == HERE:
            return importlib.import_module("cellbench.families." + name)
        key = "cellbench_family_" + name.replace(".", "_").replace("-", "_")
        mod = sys.modules.get(key)
        if mod is not None and getattr(mod, "__file__", None) == path:
            return mod
        if os.path.dirname(path) not in sys.path:
            sys.path.append(os.path.dirname(path))
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod
    raise SystemExit(f"no families/{name}.py in any of "
                     f"{list(dirs) + [HERE]}")


def load(config_file: str, dirs=()):
    """The deployment of a configuration file: its family's ``Deployment``,
    with the family module as ``.family``."""
    spec = load_json(config_file)
    fam = family(spec.get("family", "flow"), dirs)
    dep = fam.Deployment(spec)
    dep.family = fam
    return dep

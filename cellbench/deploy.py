"""A deployment, read from ``cellbench/configs/<name>.json``.

JAX-free: the generators, the probe and the reference read the same rule
layout from here that ``cellbench/server.py`` loads into the service.

Layout. Plain flows have ids ``0 .. n_plain-1`` and flow ``i`` belongs to
namespace ``ns{i % namespaces}``; inside a namespace a flow's popularity rank
is ``i // namespaces``. The hottest ranks of every namespace are metered at
the finite counts ``rules.metered_counts`` (rank 0 first); every other plain
flow carries ``rules.unmetered_count``, which no traffic reaches. The probe's
flows have ids from ``PROBE_BASE`` up and live in the first probe namespace;
the second probe namespace is kept idle for the namespace-guard check. No
traffic mix touches a probe namespace.
"""

from __future__ import annotations

import json
import os

import numpy as np

PROBE_BASE = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))

# statuses (TokenResultStatus of the reference, plus this server's own)
OK, BLOCKED, SHOULD_WAIT, NO_RULE, TOO_MANY, FAIL = 0, 1, 2, 3, 4, 5
OVERLOAD, STANDBY, MOVED, DEGRADED = 8, 9, 10, 12
# a verdict the device decided; everything else is a failure (ISSUE A.3)
DECISIONS = (OK, BLOCKED, SHOULD_WAIT, NO_RULE, TOO_MANY, DEGRADED)
# control behaviours of a rule (RuleConstant of the reference)
DEFAULT, WARM_UP, RATE_LIMITER, WARM_UP_RATE_LIMITER = 0, 1, 2, 3


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Deployment:
    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        r = spec["rules"]
        self.namespaces = int(r["namespaces"])
        self.n_flows = int(r["n_flows"])
        self.unmetered_count = float(r["unmetered_count"])
        self.metered_counts = [float(c) for c in r["metered_counts"]]
        self.probe_namespaces = [int(n) for n in r["probe_namespaces"]]
        self.probe = r["probe"]
        self.ns_max_qps = float(spec["ns_max_qps"])
        self.window_ms = (int(spec["engine"]["bucket_ms"])
                          * int(spec["engine"]["n_buckets"]))
        self.bucket_ms = int(spec["engine"]["bucket_ms"])
        self.probe_rules = self._probe_rules()
        self.n_plain = self.n_flows - len(self.probe_rules)
        if self.n_plain < self.namespaces * (len(self.metered_counts) + 1):
            raise ValueError("too few plain flows for the metered ranks")

    # -- plain flows -------------------------------------------------------
    def traffic_namespaces(self) -> list:
        return [n for n in range(self.namespaces)
                if n not in self.probe_namespaces]

    def flows_per_namespace(self) -> int:
        """Ranks every namespace has (the last, ragged rank is left out)."""
        return self.n_plain // self.namespaces

    def flow_id(self, ns, rank):
        return np.asarray(ns, np.int64) + self.namespaces * np.asarray(
            rank, np.int64)

    def is_metered(self, flow_ids) -> np.ndarray:
        f = np.asarray(flow_ids, np.int64)
        return (f < PROBE_BASE) & (f // self.namespaces
                                   < len(self.metered_counts))

    def metered_index(self, flow_ids) -> np.ndarray:
        """Dense index of a metered plain flow: ``ns * n_ranks + rank``."""
        f = np.asarray(flow_ids, np.int64)
        return (f % self.namespaces) * len(self.metered_counts) + (
            f // self.namespaces)

    def metered_count_of_index(self) -> np.ndarray:
        return np.tile(np.asarray(self.metered_counts),
                       self.namespaces)

    # -- probe flows -------------------------------------------------------
    def _probe_rules(self) -> list:
        """``(flow_id, count, behaviour, role)`` of the probe's own flows."""
        p = self.probe
        out = []
        fid = PROBE_BASE
        for _set in range(int(p["sets"])):
            for c in p["tight_counts"]:
                out.append((fid, float(c), DEFAULT, "tight"))
                fid += 1
            out.append((fid, float(p["big_count"]), DEFAULT, "big"))
            fid += 1
            out.append((fid, float(p["paced_count"]), RATE_LIMITER, "paced"))
            fid += 1
        return out

    def probe_set(self, k: int) -> dict:
        per = len(self.probe["tight_counts"]) + 2
        rules = self.probe_rules[k * per:(k + 1) * per]
        return {
            "tight": [(f, c) for f, c, _b, role in rules if role == "tight"],
            "big": next((f, c) for f, c, _b, role in rules if role == "big"),
            "paced": next((f, c) for f, c, _b, role in rules
                          if role == "paced"),
        }

    def rules(self):
        """Every rule as ``(flow_id, count, namespace_name, behaviour)``."""
        nm = len(self.metered_counts)
        for i in range(self.n_plain):
            rank = i // self.namespaces
            count = (self.metered_counts[rank] if rank < nm
                     else self.unmetered_count)
            yield i, count, f"ns{i % self.namespaces}", DEFAULT
        ns = f"ns{self.probe_namespaces[0]}"
        for fid, count, behaviour, _role in self.probe_rules:
            yield fid, count, ns, behaviour


def load_deployment(name: str) -> Deployment:
    return Deployment(load_json(os.path.join(HERE, "configs", name + ".json")))

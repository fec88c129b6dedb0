"""A digest of everything one generator process would send: the encoded
frames and, in an open loop, their due times. ``data/frame_digests.json``
holds the digests taken from the tree before the families (PR 25's), for the
five flow mixes of ``cellbench/traffic/`` and seeds 1-3, and those taken from
the tree before sessions (PR 39's) for the mixes of the three other families'
cells, so that every cell's bytes and due times are pinned."""

from __future__ import annotations

import hashlib
import os

import numpy as np

from cellbench import deploy, loadgen, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG_OF = {"tenants-zipf-open": "mesh-100k", "tenants-zipf-burst": "mesh-100k",
             "zipf-hiccup": "mesh-100k", "sidecar-sat": "mesh-100k",
             "single-token": "demo-cluster-1k",
             "keys-zipf-open": "hot-param-1k",
             "tenants-zipf-prio-open": "shaped-mesh-100k",
             "tenants-zipf-health-cycle-open": "breaker-mesh-100k"}


def digest(mix: str, seed: int, proc: int) -> str:
    tr = deploy.load_json(os.path.join(ROOT, "cellbench", "traffic",
                                       mix + ".json"))
    g = loadgen.Generator({
        "traffic": tr, "seed": seed, "proc": proc, "seconds": 20.0,
        "warm_seconds": 1.5, "port_file": "unused",
        "config_file": os.path.join(ROOT, "cellbench", "configs",
                                    CONFIG_OF[mix] + ".json")})
    h = hashlib.sha256()
    if g.open:
        for due, cols in (g.main, g.warm):
            h.update(np.ascontiguousarray(due, np.float64).tobytes())
            for frame in traffic.encode_frames(g.fam, cols, g.next_xid):
                h.update(frame)
        for frame in traffic.encode_frames(g.fam, g.burst_mix.frames(16), 7):
            h.update(frame)
    elif g.single:
        pool = loadgen._SINGLE_POOL
        for ci in range(int(tr["connections"])):
            h.update(g.fam.encode_singles(0, *[
                col[ci * pool:(ci + 1) * pool, 0] for col in g.pool]).tobytes())
    else:
        for k in range(len(g.pool[0])):
            h.update(g.fam.encode_batch(k, *[col[k] for col in g.pool]))
    return h.hexdigest()

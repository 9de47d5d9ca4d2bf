"""``taxi-1b`` loads what PR 36 loaded: the body ``start`` posts to
``/schema`` and the bits ``load`` leaves in every fragment, at the
rehearsal size and seed 2147483777, equal what this configuration and
generator posted and left when they were added.  The guard against a
deployment that moved under a later change to the harness, the
generator or the program's imports.

``data/taxi-1b.identity.json`` was recorded by

    JAX_PLATFORMS=cpu python benchmark/tests/test_taxi_identity.py > benchmark/tests/data/taxi-1b.identity.json
"""

import hashlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2147483777


def fingerprint() -> dict:
    from harness import server
    config = server.load_json("configs", "taxi-1b.json")
    params = config["params"]
    gen = server.load_module("generators", config["generator"])
    posted = []
    real = server.Http.call

    def call(self, method, path, body=None):
        if (method, path) == ("POST", "/schema"):
            posted.append(json.dumps(body))
        return real(self, method, path, body)

    server.Http.call = call
    try:
        srv, http_ = server.start(config)
    finally:
        server.Http.call = real
    try:
        server.load(srv, config, gen, SEED, params["rehearsal_shards"])
        digest, n = hashlib.sha256(), 0
        fields = srv.holder.index(params["index"]).fields
        for fname in sorted(fields):
            for vname, view in sorted(fields[fname].views.items()):
                for shard, frag in sorted(view.fragments.items()):
                    for row in frag.row_ids:
                        digest.update(
                            f"{fname}/{vname}/{shard}/{row}".encode())
                        digest.update(np.ascontiguousarray(
                            frag.row_words(row), dtype=np.uint32).tobytes())
                        n += 1
    finally:
        http_.close()
        srv.close()
    return {"seed": SEED, "shards": params["rehearsal_shards"],
            "schema_body": posted[0], "rows": n,
            "row_words_sha256": digest.hexdigest()}


def test_taxi_posts_the_same_schema_and_loads_the_same_bits():
    with open(os.path.join(HERE, "data", "taxi-1b.identity.json")) as f:
        recorded = json.load(f)
    assert fingerprint() == recorded


if __name__ == "__main__":
    for path in (os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
        sys.path.insert(0, path)
    print(json.dumps(fingerprint(), indent=1))

"""The one general traffic generator's rules, shared by the client processes
(`loadgen.py`, which sends) and the parent (`run.py`, which checks).

A traffic mix is a data file, `benchmark/traffic/<mix>.json`; nothing here
knows a cell by name.  Everything a client sends is a pure function of
(--seed, client index, the file's parameters):

  - the order of operations: blocks of sum(mix) operations, each block holding
    every operation kind exactly as often as the mix says, in an order drawn
    from the seed.  So every seed does the same work in another order;
  - which object a GET / STAT / DELETE picks: uniformly from the client's live
    keys, drawn from the seed;
  - an object's bytes: a 32-byte tag, SHA-256 of (seed, key, part, generation),
    followed by one of the client's few seeded pool buffers from byte 32 on,
    the buffer chosen by the tag.  A body is never kept: whoever knows the
    key, the part and the generation can make it again;
  - with `hide_shards` = h: which h data shards of each prefilled object the
    parent takes away before the warm-up (`hidden_shards`, `run.hide_shards`).
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

import reference

HERE = os.path.dirname(os.path.abspath(__file__))
TAG = 32
OPS = ("PUT", "GET", "STAT", "DELETE")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(bench: dict, cell: str,
              traffic_dir: str | None = None) -> tuple[dict, dict]:
    """(traffic mix, configuration) of a cell: `BENCHMARK.json` names both,
    `traffic/<traffic>.json` and `configs/<config>.json` hold them.
    `traffic_dir` puts another directory in `traffic/`'s place: the
    rehearsal's small mixes under the same names."""
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise ValueError(f"BENCHMARK.json has no cell {cell!r}")
    wl = load_json(traffic_dir or "traffic", f"{entry['traffic']}.json")
    for key in ("clients", "object_bytes", "part_bytes", "mix",
                "prefill_per_client", "put_key_ring", "pool_buffers",
                "check"):
        if key not in wl:
            raise ValueError(f"traffic/{entry['traffic']}.json: no {key!r}")
    if set(wl["mix"]) - set(OPS) or not any(wl["mix"].values()):
        raise ValueError(f"traffic/{entry['traffic']}.json: mix {wl['mix']}")
    for key in ("readback_objects", "disk_parts", "deleted_gets"):
        if key not in wl["check"]:
            raise ValueError(f"traffic/{entry['traffic']}.json: no check.{key}")
    if wl["part_bytes"] and wl["object_bytes"] % wl["part_bytes"]:
        raise ValueError(f"traffic/{entry['traffic']}.json: object_bytes is "
                         f"not a whole number of parts")
    cfg = load_json("configs", f"{entry['config']}.json")
    for key in ("drives", "data_shards", "parity_shards", "chips",
                "storage_class_standard", "put_headers", "env",
                "server_args"):
        if key not in cfg:
            raise ValueError(f"configs/{entry['config']}.json: no {key!r}")
    # The digest the server frames shard blocks with is the configuration's
    # one statement: the server is told it (`run.Server`), and the reference
    # and the rooflines follow it.  A second statement could disagree.
    algo = cfg.setdefault("bitrot_algo", reference.DEFAULT_ALGO)
    if algo not in reference.ALGOS:
        raise ValueError(f"configs/{entry['config']}.json: bitrot_algo "
                         f"{algo!r} is not one of {sorted(reference.ALGOS)}")
    if "MTPU_BITROT_ALGO" in cfg["env"]:
        raise ValueError(f"configs/{entry['config']}.json: env names "
                         f"MTPU_BITROT_ALGO: say it as bitrot_algo, once")
    hide = wl.get("hide_shards", 0)
    if hide:
        # Shards taken away between prefill and warm-up (`run.hide_shards`).
        # What the window wrote would be healthy, and what it deleted could
        # not be told from what was hidden: loss under writes is a later
        # cell's.  The step knows single-part objects under new keys.
        refused = [why for bad, why in (
            (not 0 < hide <= cfg["parity_shards"],
             f"not from 1 to the {cfg['parity_shards']} parity shards of "
             f"configs/{entry['config']}.json"),
            (not wl["prefill_per_client"], "nothing is prefilled"),
            (wl["mix"].get("PUT") or wl["mix"].get("DELETE"),
             "the mix writes or deletes"),
            (wl["part_bytes"] or wl["put_key_ring"],
             "objects are multipart or on a key ring")) if bad]
        if refused:
            raise ValueError(f"traffic/{entry['traffic']}.json: hide_shards "
                             f"{hide}: {'; '.join(refused)}")
    if cfg["chips"] != entry["chips"]:
        raise ValueError(f"cell {cell!r} asks for {entry['chips']} chip(s), "
                         f"its configuration maps onto {cfg['chips']}")
    return wl, cfg


def load_metric(name: str) -> dict:
    """How a per-layer metric is read: `metrics/<name>.json`.  A quantity
    split by the end-to-end metric it moves (`<quantity>.put`,
    `<quantity>.get`) is read one way: `metrics/<quantity>.json`."""
    for stem in (name, name.rpartition(".")[0]):
        if stem and os.path.exists(os.path.join(HERE, "metrics",
                                                f"{stem}.json")):
            return load_json("metrics", f"{stem}.json")
    raise ValueError(f"no metrics/{name}.json")


def body_bytes(wl: dict) -> int:
    """Bytes of one request body: a part, or a whole object."""
    return wl["part_bytes"] or wl["object_bytes"]


def parts_of(wl: dict) -> list[int]:
    """Part numbers of one object; [0] for a single PUT."""
    if not wl["part_bytes"]:
        return [0]
    return list(range(1, wl["object_bytes"] // wl["part_bytes"] + 1))


class Bodies:
    """One client's pool buffers and the bodies derived from them."""

    def __init__(self, seed: int, client: int, wl: dict):
        self.seed, self.client = seed, client
        self.size = body_bytes(wl)
        self.npool = wl["pool_buffers"]
        self._pool: dict[int, memoryview] = {}

    def buffer(self, j: int) -> memoryview:
        if j not in self._pool:
            rng = np.random.default_rng([self.seed, self.client, j])
            self._pool[j] = memoryview(rng.bytes(self.size))
        return self._pool[j]

    def fill(self) -> None:
        for j in range(self.npool):
            self.buffer(j)

    def chunks(self, key: str, part: int, gen: int) -> list:
        """The body of (key, part, generation) as [tag, rest of a pool
        buffer]: sent chunk by chunk, compared chunk by chunk."""
        tag = hashlib.sha256(
            f"{self.seed}/{key}#{part}@{gen}".encode()).digest()
        j = int.from_bytes(tag[:4], "big") % self.npool
        return [tag, self.buffer(j)[TAG:]]

    def matches(self, got: bytes, key: str, gen: int, wl: dict) -> bool:
        """Whether `got` is the whole object (key, generation)."""
        got = memoryview(got)
        n = self.size
        if len(got) != n * len(parts_of(wl)):
            return False
        for i, part in enumerate(parts_of(wl)):
            tag, rest = self.chunks(key, part, gen)
            if got[i * n:i * n + TAG] != tag or got[i * n + TAG:(i + 1) * n] \
                    != rest:
                return False
        return True


def op_blocks(seed: int, client: int, mix: dict):
    """The client's endless sequence of operation kinds."""
    block = [op for op in OPS for _ in range(int(mix.get(op, 0)))]
    rng = np.random.default_rng([seed, client, 0xA11])
    while True:
        yield from (block[i] for i in rng.permutation(len(block)))


def picker(seed: int, client: int):
    """Uniform picks from a list that changes: pick(n) -> index < n."""
    rng = np.random.default_rng([seed, client, 0xB0B])
    return lambda n: int(rng.integers(n))


def start_offset(seed: int, client: int, wl: dict) -> float:
    """Seconds after the window opens at which this client sends its first
    request: the clients share `stagger_s` evenly, in an order drawn from the
    seed, so that a closed loop does not begin in lock-step."""
    order = np.random.default_rng([seed, 0x57A6]).permutation(wl["clients"])
    return wl.get("stagger_s", 0.0) * int(order[client]) / wl["clients"]


def hidden_shards(seed: int, client: int, n: int, k: int, h: int) -> list[int]:
    """Which h of the k data shards (0-based) of the client's n-th
    prefilled object are taken away: drawn from (seed, object), so the
    sets differ from object to object and from seed to seed."""
    rng = np.random.default_rng([seed, client, n, 0x41DE])
    return sorted(int(i) for i in rng.permutation(k)[:h])


def ring_key(client: int, i: int) -> str:
    return f"c{client}/ring-{i}"


def new_key(client: int, n: int) -> str:
    return f"c{client}/o-{n}"

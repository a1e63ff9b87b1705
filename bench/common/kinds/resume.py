"""Resume from a selective checkpoint chain (``"kind": "resume"``).

Set-up draws two train states from the seed and saves them through the
program's save path: a state at ``base_step`` as event 0 (every unit),
then another at ``event_step`` as event 1 under ``policy``.  A unit of
the window is one resume as a relaunched trainer makes it: a new
checkpoint manager on the chain, ``restore`` of the whole train state,
every leaf ready on the device.

Compared, after the window: ``restore_mismatch``, the (leaf, unit)
pairs of every resumed state that differ from the composite the chain
holds (each unit as the last event that saved it left it), summed over
the resumes.

The chain sits in the program's RAM tier (``program.ram_store``), where
a same-host relaunch would find it in the page cache.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

import jax

from bench.common import composite, program, stategen
from bench.common.checksum import make_checksum, mismatches, to_host


class Driver:
    def __init__(self, *, config: Dict, traffic: Dict, seed: int, root,
                 rec):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.root, self.rec = Path(root), rec
        self.model = program.build(config)
        self.specs = program.state_specs(self.model)
        self.units = program.units(self.model, config["name"])
        self.roots = program.stacked_roots(self.model)
        self.key = stategen.seed_key(seed)
        self.store = program.ram_store()
        self.records: Dict = {}
        self.got, self.restore_stats = [], []

    def write_chain(self, checksum) -> Dict[int, Dict]:
        """Save the two events; returns their live checksums."""
        make = stategen.make_state_fn(self.specs["params"], self.roots,
                                      moments=True)
        mgr = program.manager(self.root, self.model, self.tr["policy"],
                              self.store, codec=self.tr["codec"])
        sums = {}
        try:
            for event, step in enumerate((self.tr["base_step"],
                                          self.tr["event_step"])):
                state = make(jax.random.fold_in(self.key, event), step)
                sums[event] = to_host(checksum(state))
                mgr.save(state, step=step)
                del state
        finally:
            mgr.close()
        return sums

    def setup(self):
        self.checksum = make_checksum(self.roots)
        sums = self.write_chain(self.checksum)
        self.want = composite.expected(self.tr["policy"], 1, sums,
                                       self.units)
        reader = program.reader(self.root, self.model, self.store)
        try:
            reader.restore(self.specs, units=("final_norm",))
        finally:
            reader.close()

    def unit(self, i: int) -> None:
        with self.rec.span("resume"):
            reader = program.reader(self.root, self.model, self.store)
            state = reader.restore(self.specs)
            jax.block_until_ready(state)
        reader.close()
        self.restore_stats.append(dict(reader.last_restore_stats))
        self.got.append(self.checksum(state))
        del state

    def after_window(self) -> None:
        self.records.update(restore_stats=self.restore_stats)

    def end_to_end(self, records: Dict) -> Dict:
        return {"resume_s": records["window_s"] / records["units"]}

    def check(self):
        bad = [mismatches(to_host(g), self.want) for g in self.got]
        return ({"restore_mismatch": sum(bad)}, len(bad),
                sum(1 for b in bad if b))

    def close(self):
        self.store = None

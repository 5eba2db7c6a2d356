#!/usr/bin/env python3
"""The control of ``correct``: a run of the cell with the plain reference,
computed one step below the precision the configurations state, answering
in the program's place.

The configurations state exact answers over 64-bit values (datetimes are
epoch microseconds in int64).  The control compares, sums and takes
extremes in 32-bit float lanes on the device (the step that tempts: a
Pallas kernel over f32 tiles, which rounds an epoch-microsecond datetime
to about 134 s).  Everything else is the cell's own run: its set-up, its
window and clients, and its checks (``run.Cell._check_answers``), which
have to report ``correct`` false.

    python3 bench/control.py --workload <cell> --seconds <s> --seed <n> [--seed <n> ...]

prints one result line per seed.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import gen, reference, run, traffic  # noqa: E402


class Reference32(reference.Reference):
    """Masks, sums and extremes in float32 on the device; datetimes as
    float32 epoch microseconds."""

    def _us(self, col: str, x: np.ndarray) -> np.ndarray:
        t0 = {"timestamp": gen.MSG_T0, "user-since": gen.USER_T0}.get(col)
        return x if t0 is None else gen.epoch_us(t0, x)

    def _mask(self, ds, rng, among=None):
        import jax.numpy as jnp
        col = rng[0]
        x = jnp.asarray(self._us(col, self.cols[ds][col]), jnp.float32)
        lo = jnp.float32(self._us(col, np.asarray([rng[1]]))[0])
        hi = jnp.float32(self._us(col, np.asarray([rng[2]]))[0])
        m = np.asarray((x >= lo) & (x <= hi))
        return m if among is None else m & among

    def _agg(self, ds, aggs, idx):
        import jax.numpy as jnp
        row: Dict[str, Any] = {}
        for name, (fn, col) in aggs.items():
            if fn == "count":
                row[name] = int(idx.size)
                continue
            v = jnp.asarray(self._us(col, self._value(ds, col, idx)),
                            jnp.float32)
            if fn == "sum":
                row[name] = int(jnp.sum(v)) if idx.size else 0
                continue
            if idx.size == 0:
                row[name] = None
                continue
            us = int(jnp.min(v) if fn == "min" else jnp.max(v))
            if col in ("timestamp", "user-since"):
                row[name] = gen.EPOCH + dt.timedelta(microseconds=us)
            else:
                row[name] = us
        return row


class ControlCell(run.Cell):
    """The cell's run with ``Reference32`` answering every request in
    the program's place.  A read cell builds nothing of the program (no
    answer reads it); the ingest cell builds and feeds the deployment as
    a run does, since its checks read the program's snapshots and
    records, and skips only the recovery, whose probe the control
    answers."""

    def setup(self) -> None:
        if self.mix["loop"] == "closed":
            self.dep = run.Deployment(self.cfg, self.seed)
            self.dom = traffic.domains(self.dep.n_users)
            self.templates = {t["name"]: t for t in self._all_templates()}
            msgs = self.dep.msgs
        else:
            super().setup()
            msgs = self._all_messages()
        self.low = Reference32(self.dep.users, msgs)

    def _warmup(self) -> None:
        pass

    def _recover(self, ds) -> None:
        pass

    def _execute(self, r: run.Request, plan, datasets: Dict[str, Any]
                 ) -> None:
        r.t_issue = time.perf_counter()
        r.rows = self.low.answer(r.req, self.templates[r.req["template"]],
                                 among=r.among)
        r.t_done = time.perf_counter()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    for seed in args.seed:
        line = run.run_cell(spec, args.workload, seed, args.seconds, False,
                            cell_cls=ControlCell)
        line["seed"] = seed
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

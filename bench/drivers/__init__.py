"""Drivers: what is particular to one kind of deployment.

A configuration file (``bench/configs/<config>.json``) names its driver
under the optional key ``"driver"``; without the key it is
``block_sweep``. The driver is the file ``bench/drivers/<driver>.py`` of
the checkout, loaded once per run, in set-up. The harness
(``bench/run.py``) keeps what holds for any deployment: the cell's
files, the chip check, the timed window and its trace, the reference's
process pool, the comparison, the metric readers and the result line.
So a deployment with new semantics (its own request columns, program
call, counts and plain reference) comes as new files: a driver, a
configuration naming it, a traffic mix and a cell.

A driver module defines at its top level:

* ``check(config)``: raise where the driver or its reference cannot take
  the configuration. Set-up calls it first, so such a configuration
  fails before the window, not after it.
* ``program(config)``: the program under test, as ``warm_up`` and
  ``run_job`` take it.
* ``make_pool(cell, seed)``: the seed's distinct jobs, each a list of
  volumes (``cell`` is ``run.Cell``: its ``config``, ``traffic`` and
  ``shape``). The same seed gives the same volumes.
* ``warm_up(program, shape)``: compile and run every program the
  window's jobs call, at the cell's shapes. Nothing compiles in the
  window.
* ``run_job(program, volumes, shape)``: one timed job, run to its end.
  It returns a dict: ``counts``, an int64 array ``(volumes, k)`` of
  per-volume counts, with whatever ``k`` the deployment needs;
  ``requests``, the job's requests; ``n_slabs``, ``lane_steps`` and
  ``ideal_lane_steps``, the program's schedule (lane-steps executed, and
  those that carried a request); ``stats``, a dict of the program's own
  counters and spans, which may be empty; ``compiles``, the programs the
  call compiled, which the harness refuses above 0. The metric readers
  read these fields (``run.Job``). The harness runs the call under the
  host span ``job``; a read-back from the device inside it goes under
  ``readback``. Neither names an idle gap of the trace.
* ``reference(config, shape, volume, control)``: one volume's row of
  counts from the driver's plain reference, which imports nothing of
  the program; ``control`` is a key of ``CONTROLS``, empty for the
  reference itself.
* ``CONTROLS``: ``{name: the stated guarantee it breaks}``, with ``""``
  for the reference. ``bench/control.py`` checks each as a run checks
  the program; each must come out not correct.

Two rules follow from where the reference runs: on a pool of spawned
host processes (``run.reference_counts``), each of which loads the
driver's file to find ``reference`` and must never import JAX.

* The module imports no JAX at its top level; its functions import the
  program where they run it.
* A volume is sent to those processes by pickle: build it from NumPy
  arrays, built-in types and the types of ``bench/lib``
  (``generate.Volume``), never from a class of the driver's own module,
  which a spawned process cannot import by name.

A driver may use ``bench/lib/generate.py``'s helpers (``volume_seed``,
``volume_offset``, ``Volume``) and bring its own generator in its own
file; it does not edit them.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from types import ModuleType

DEFAULT = "block_sweep"


@functools.lru_cache(maxsize=None)
def _load_file(path: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_driver_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(root: Path, config: dict) -> ModuleType:
    """The driver that ``config`` names, from ``<root>/bench/drivers``."""
    name = config.get("driver", DEFAULT)
    path = Path(root) / "bench" / "drivers" / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier()
            and path.is_file()):
        raise LookupError(f"configuration {config.get('name')!r} names "
                          f"the driver {name!r}, which is not a file "
                          f"{path}")
    return _load_file(str(path))


def reference_row(path: str, config: dict, shape: dict, volume,
                  control: str):
    """One volume's reference counts from the driver at ``path``: the
    task a reference worker runs."""
    return _load_file(path).reference(config, shape, volume, control)

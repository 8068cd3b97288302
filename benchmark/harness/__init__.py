"""The benchmark's own yardstick: traffic, reference, peaks, work counts and
the reduction from traces to metrics. Nothing here imports the program except
the drivers, which take from it only the system under test.

Everything that belongs to one kind of traffic or one family of models is a
file of its own, found by the name the data gives: a traffic file's ``kind``
names its driver (``<kind>_driver.py``), a configuration's ``family`` the
adapter to the program's flags and parameter tree (``family_<family>.py``)
and its ``reference`` the plain reference (``<reference>.py``). A later PR
adds a kind, a family or a reference as a new file and edits none."""

import importlib
import os


def by_name(module: str):
    """``benchmark/harness/<module>.py``, or an error that names the file a
    later PR has to add."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        module + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: {os.path.relpath(path)} does not "
                         f"exist; the data names it, so add it")
    return importlib.import_module(f"{__name__}.{module}")


def driver_for(traffic):
    return by_name(traffic["kind"] + "_driver")


def family_for(cfg):
    return by_name("family_" + cfg["family"])


def reference_for(cfg):
    return by_name(cfg["reference"])

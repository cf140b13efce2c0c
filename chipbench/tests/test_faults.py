"""The timed path broken underneath a whole run (the chip look
skipped): each fault a cell can have makes ``correct`` come out false.
One chip, so no exchange between chips can be left out."""
import numpy as np
import pytest

from chipbench import run

from .conftest import tiny_cell


def _put_unchanged(mp):
    """A Put acknowledged with the store left as it was."""
    from repro.core.lsm.storage import LSMStore
    mp.setattr(LSMStore, "write_batch",
               lambda self, tree, keys, vals=None, **kw: None)


def _put_half(mp):
    """Half of each Put batch left out."""
    from repro.core.lsm.storage import LSMStore
    orig = LSMStore.write_batch

    def half(self, tree, keys, vals=None, **kw):
        n = (len(keys) + 1) // 2
        return orig(self, tree, keys[:n], None if vals is None else vals[:n],
                    **kw)
    mp.setattr(LSMStore, "write_batch", half)


def _put_altered(mp):
    """One value of each Put batch altered where it is ingested."""
    from repro.core.lsm.storage import LSMStore
    orig = LSMStore.write_batch

    def altered(self, tree, keys, vals=None, **kw):
        vals = np.array(vals, copy=True)
        vals[0] += 1
        return orig(self, tree, keys, vals, **kw)
    mp.setattr(LSMStore, "write_batch", altered)


def _get_half(mp):
    """Half of each Get batch answered, the rest left out as misses."""
    from repro.core.lsm.tree import LSMTree
    orig = LSMTree.lookup_batch

    def half(self, keys):
        found, vals = orig(self, keys)
        found[len(keys) // 2:] = False
        vals[len(keys) // 2:] = 0
        return found, vals
    mp.setattr(LSMTree, "lookup_batch", half)


def _get_altered(mp):
    """One answer of each Get batch altered where the tree produces it."""
    from repro.core.lsm.tree import LSMTree
    orig = LSMTree.lookup_batch

    def altered(self, keys):
        found, vals = orig(self, keys)
        vals[np.argmax(found)] += 1
        return found, vals
    mp.setattr(LSMTree, "lookup_batch", altered)


FAULTS = {
    "lookup.gpulsm-16m-log256m": (_put_unchanged, _get_half, _get_altered),
    "update.gpulsm-16m-log256m": (_put_unchanged, _put_half, _put_altered),
}


@pytest.mark.parametrize("name,fault", [
    (name, f) for name, faults in FAULTS.items() for f in faults],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    fault(monkeypatch)
    res = run.run_cell(tiny_cell(name), 2**31 + 7, 1.0, False,
                       require_tpu=False)
    assert res["correct"] is False
    assert max(c["value"] for c in res["checks"].values()) > 0

"""Faults of the write path, planted under the timed path to show that
the check of a run under writes catches each.  Each planter takes the
``setattr`` to plant with: a test passes pytest's ``monkeypatch.setattr``,
so the fault is undone after it; ``prove.py --fault`` plants with the
builtin ``setattr`` in a process that serves nothing else.

* ``ack_without_apply`` — each batch is acknowledged with the next epoch
  and not applied (``lost_writes``);
* ``read_at_newest`` — every in-flight read sweeps the newest edges, not
  those of its admission epoch (``wrong_answers``, where a batch applied
  during a read changes its answer);
* ``admission_lags`` — admission sees the graph as it was before the
  latest batch (``stale_reads``).
"""
from typing import Callable

Setattr = Callable[[object, str, object], None]


def ack_without_apply(set_attr: Setattr = setattr) -> None:
    from repro.core.scheduler import SlotScheduler
    set_attr(SlotScheduler, "submit_update",
             lambda self, add=None, remove=None: self.engine.epoch + 1)


def read_at_newest(set_attr: Setattr = setattr) -> None:
    from repro.core import dense
    orig = dense.DenseStepper.step

    def step(self):
        for slot in self.slots:
            slot.edges = self.eng._edges()
        return orig(self)
    set_attr(dense.DenseStepper, "step", step)


def admission_lags(set_attr: Setattr = setattr) -> None:
    from repro.core.scheduler import SlotScheduler
    update, admit = SlotScheduler.submit_update, SlotScheduler._admit

    def submit_update(self, add=None, remove=None):
        self._lagged = (self.engine.delta, self.engine._eff)
        return update(self, add=add, remove=remove)

    def lagging_admit(self, now):
        eng = self.engine
        if getattr(self, "_lagged", None) is None:
            return admit(self, now)
        now_state = (eng.delta, eng._eff)
        eng.delta, eng._eff = self._lagged
        try:
            return admit(self, now)
        finally:
            eng.delta, eng._eff = now_state
    set_attr(SlotScheduler, "submit_update", submit_update)
    set_attr(SlotScheduler, "_admit", lagging_admit)


FAULTS = {f.__name__: f for f in (ack_without_apply, read_at_newest,
                                  admission_lags)}

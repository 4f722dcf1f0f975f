"""Fixture compile-cache engine. Seeded: both _cached_program sites
(lambda build and loop-nested local-def build) read HLL_LOG2M during
program build while the signature only folds TZ_ID —
compile-sig-missing-config. ``run_wave`` seeds the pallas variant: the
wave-program build reads PALLAS_TILE_BYTES (a kernel tiling knob that
changes the compiled program) but the sig never folds it.
``run_helper`` takes its signature's common part from ``_sig_base``:
the helper folds TZ_ID, which the build reads too (no finding), and
lacks HLL_LOG2M (a finding) — K1 reads through the helper."""

from utils.config import HLL_LOG2M, PALLAS_TILE_BYTES, TZ_ID


class Engine:
    def __init__(self, config):
        self.config = config
        self._programs = {}

    def _cached_program(self, sig, build):
        prog = self._programs.get(sig)
        if prog is None:
            prog = self._programs[sig] = build()
        return prog

    def _build_prog(self, q):
        return ("prog", q.datasource, self.config.get(HLL_LOG2M))

    def run(self, q):
        sig = ("agg", q.datasource, self.config.get(TZ_ID))
        prog = self._cached_program(sig, lambda: self._build_prog(q))
        while True:
            def build():
                return self._build_prog(q)

            prog2 = self._cached_program(sig, build)
            return prog, prog2

    def _build_wave(self, q):
        return ("wave", q.datasource, self.config.get(PALLAS_TILE_BYTES))

    def run_wave(self, q):
        sig = ("wave", q.datasource, self.config.get(TZ_ID))
        return self._cached_program(sig, lambda: self._build_wave(q))

    def _sig_base(self, q):
        return (q.datasource, self.config.get(TZ_ID))

    def _build_both(self, q):
        return ("both", self.config.get(TZ_ID),
                self.config.get(HLL_LOG2M))

    def run_helper(self, q):
        sig = ("agg2", self._sig_base(q))
        return self._cached_program(sig, lambda: self._build_both(q))

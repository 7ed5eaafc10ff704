"""Drive the Tor cell on the CPU at its tiny size, as ``drive_cpu.py``
does, with a fault of the Tor path planted first.

    python drive_tor_cpu.py <fault> <seconds> <work dir>

Faults: none; hop_off_by_one (every circuit's middle hop is the relay
after the one the weighted draw chose); drop_relayed_byte (plain relays
forward on the Python path, the first DATA body each forwards one byte
short). A C engine with another initial window is no plant: its test
builds one and points ``SHADOW_TPU_COLCORE_SO`` at it.
"""

import sys

import drive_cpu


def plant(fault: str) -> None:
    from shadow_tpu.models import tor

    if fault == "hop_off_by_one":
        draw = tor.RelayTable.draw

        def shifted(self, rng, pos, chosen):
            r = draw(self, rng, pos, chosen)
            if pos:
                return r
            ids = self.elig[pos]
            j = ids.index(r)
            for k in range(1, len(ids)):
                if ids[(j + k) % len(ids)] not in chosen:
                    return ids[(j + k) % len(ids)]
            return r

        tor.RelayTable.draw = shifted
    elif fault == "drop_relayed_byte":
        start, on_body = tor.TorRelay.start, tor.TorRelay._on_body

        def python_start(self):
            if type(self) is tor.TorRelay:
                self.api.listen(self.port, self._on_accept)
            else:
                start(self)

        def short(self, cid, circ, nbytes):
            if not getattr(self, "_short", False) and nbytes > 1:
                self._short = True
                nbytes -= 1
            on_body(self, cid, circ, nbytes)

        tor.TorRelay.start = python_start
        tor.TorRelay._on_body = short
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, seconds, work = sys.argv[1:4]
    import run

    drive_cpu.shrink(run, work)
    plant(fault)
    sys.exit(run.main(["--workload", "tor_1_10.web20k", "--seed",
                       str(drive_cpu.SEED), "--seconds", seconds,
                       "--trace", "0"]))

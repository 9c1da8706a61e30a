"""% of the classified stretch in which the chip ran no operation because the
network's executor was in none of its waiting or calling spans: its own turn-
around between two dispatches (handoff, finish, publish, unspanned Python).

The stretch is what `benchmarks/hostspans.py` classifies: the first
`exec{K}.model_call` to the last `exec{K}.device_sync` of the capture, not the
window `device_idle_pct` divides by. The three `idle_*_pct` add up to the chip's
idle share of it. None where the host spans fail the clock check."""

NAME = "idle_host_loop_pct.open"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "device"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.idle_pct(facts, hostspans.HOST_LOOP)

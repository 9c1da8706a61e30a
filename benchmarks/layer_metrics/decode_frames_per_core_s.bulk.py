"""Frames a worker of the native decode pool decodes in a second of its own
busy time: `decode_frames / decode_busy_s` of the run's `BenchmarkResult` (the
pool's `steady_clock` nanoseconds inside the decoder, summed over the workers).
None on a program whose pool keeps no count."""

NAME = "decode_frames_per_core_s.bulk"
UNIT = "frames/s"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "native decode and host"
MOVES = "videos_per_s"


def read(facts):
    busy = getattr(facts.result, "decode_busy_s", 0.0)
    frames = getattr(facts.result, "decode_frames", 0)
    return frames / busy if busy and frames else None

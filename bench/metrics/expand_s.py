"""Host preprocessing: seconds of the expand stage (each partition's
2-hop closure), as the program reports it (``bench/stages.py``), inside
set-up."""
from bench import stages


def read(rec):
    return stages.seconds.get("expand")

"""Host preprocessing: seconds of the partition stage (the vertex-cut
partitioner and the replication factor), as the program reports it
(``bench/stages.py``), inside set-up."""
from bench import stages


def read(rec):
    return stages.seconds.get("partition")

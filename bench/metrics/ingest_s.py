"""Host time of ``Graph.sparse_adjacency`` / ``SparseRelation.from_coo``
on the generated edge list (ingestion, ``sparse/coo.py``)."""


def read(run):
    return run.setup.get("ingest")

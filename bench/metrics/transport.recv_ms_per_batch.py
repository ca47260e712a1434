"""Milliseconds per batch the client received in the window spent reading
the data plane's response frames after their header and unpickling them:
the program's ``transport.recv`` and ``transport.decode`` spans of
``get_elements`` and ``get_element`` (``bench/program.py``)."""


def read(run):
    recv = (run.get("program") or {}).get("transport_recv_s")
    batches = run["counters"]["client_batches"]
    if recv is None or batches <= 0:
        return None
    return 1e3 * recv / batches

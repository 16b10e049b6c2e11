"""Bytes the drain copies to the host per dispatched item
(engine/bytes_to_host over router/items_dispatched)."""


def read(r):
    n = r.delta("router/items_dispatched")
    if not n:
        return None
    return r.delta("engine/bytes_to_host") / n

"""Mean time an item waits in the router between admission and dispatch
(router/queue_delay_sum_s over router/items_dispatched)."""


def read(r):
    n = r.delta("router/items_dispatched")
    if not n:
        return None
    return 1e3 * r.delta("router/queue_delay_sum_s") / n

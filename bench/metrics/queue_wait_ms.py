"""Mean time a query waited in the coalescer before its batch was flushed:
the window's ``serve_queue_wait_ms`` histogram, sum over count."""


def read(rec):
    count, total = rec["delta"].get("serve_queue_wait_ms{}", (0, 0.0))
    return total / count if count else None

"""HTTP transport: client-side median latency minus the median of the
benchmark's span around ``Gateway.handle`` (socket, HTTP parsing, JSON
encoding and the wait for a server thread)."""


def read(rec):
    span = (rec.get("spans") or {}).get("gateway_handle_p50_ms")
    client = (rec.get("client") or {}).get("p50_ms")
    if span is None or client is None:
        return None
    return client - span

"""serve.settle_share: the request plane's settle spans (artifact build
and ledger append, `serve.instrument.SPAN_SETTLE`) as a share of the
window, in %.  The served kind only."""


def read(record):
    s = record.get("serve")
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * s["settle_s"] / s["window_s"]

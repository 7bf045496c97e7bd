"""serve.launch_share: the request plane's launch spans (device calls,
`serve.instrument.SPAN_LAUNCH`) as a share of the window, in %.  The
served kind only."""


def read(record):
    s = record.get("serve")
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * s["launch_s"] / s["window_s"]

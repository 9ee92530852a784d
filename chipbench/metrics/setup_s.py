"""``setup_s``: from process start to the window's opening (data, service,
prefill and its flush, warm-up), host clock."""


def read(rec):
    return rec["setup_s"]

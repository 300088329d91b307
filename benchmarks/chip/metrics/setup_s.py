"""setup_s: seconds from the harness's start to the window's opening:
imports, device bring-up, packing the first request and the warm-up
request, compiles included."""


def read(record):
    return record["setup_s"]

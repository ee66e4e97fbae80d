"""Fault hook for ``checks/faults.py``: the benchmark's worker hook, plus one
planted fault in the worker's answers.

With ``PERFBENCH_FAULT=answer`` every share result the worker sends back
has one element altered as the worker sends it: the n-th result its
element n (mod its size), so that the shares of one codeword differ at
different places.  (The same element raised by one in every share adds a
constant to the code's polynomial, whose constant term a plan with a split
contraction decodes and throws away.)
"""
import os
import runpy

_here = os.path.dirname(os.path.abspath(__file__))
runpy.run_path(os.path.join(_here, "..", "..", "hook", "sitecustomize.py"),
               run_name="perfbench_hook")

if os.environ.get("PERFBENCH_FAULT") == "answer":
    # the worker runs as __main__ and imports send_msg from the protocol
    # module when it starts, after this hook: it picks up the altered one
    from repro.dist import protocol as _protocol

    _send_msg = _protocol.send_msg
    _sent = [0]

    def _altered(sock, header, arrays=None, **kwargs):
        if header.get("type") == "result" and header.get("ok") and arrays:
            import numpy as np

            arrays = dict(arrays)
            h = np.array(arrays["h"])
            h.flat[_sent[0] % h.size] += 1
            _sent[0] += 1
            arrays["h"] = h
        return _send_msg(sock, header, arrays, **kwargs)

    _protocol.send_msg = _altered

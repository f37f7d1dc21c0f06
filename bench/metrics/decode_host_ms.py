"""Host time per decode call in which the host is not waiting on the
device: the engine's ``serve.decode`` span seconds less those of its
``serve.device_wait`` child, per ``serve.decode`` span, over the window
(engine spans, ``stats()["spans"]``). None where the engine keeps none."""


def _spans(rec, end):
    return rec["stats"][end].get("spans") or {}


def compute(rec):
    w0, w1 = _spans(rec, "w0"), _spans(rec, "w1")

    def delta(name, key):
        return w1.get(name, {}).get(key, 0) - w0.get(name, {}).get(key, 0)

    n = delta("serve.decode", "n")
    if n <= 0:
        return None
    return 1e3 * (delta("serve.decode", "s")
                  - delta("serve.device_wait", "s")) / n

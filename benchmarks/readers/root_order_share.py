"""Share of the waves LANDED in the traced window whose scheduler
iteration (the ``hetu.serve.wave`` root that holds the wave's
``serve.wave.sync``) says an ``order=`` that is any of ``orders``.
``inorder`` is the iteration that did NOT run a wave ahead: the device
waits out the host's unpack, admission and assembly after it.

One root a wave: an in-order landing that retires somebody returns at
once, and the iteration after it launches with nothing in flight
(``first``), so counting the roots that launched OR landed a wave counts
such a wave twice (my chip run, PR 40: 14.8 % of the roots where 8.0 %
of the waves were not run ahead).  A ``first`` root lands nothing and so
never counts here; an earlier line (``root_orders``) has every root by
order, and the waves whose own dispatch says ``ahead``: this metric is
100 less their share of the waves, to the window's edges."""

from benchmarks import wave_trace


def read(data, orders):
    w = wave_trace.waves(data)
    landed = w and [r for r in w["roots"] if r["landed"] is not None]
    if not landed:
        return None
    harness = data.get("harness")
    if harness is not None:
        by_order = {}
        for r in w["roots"]:
            by_order[str(r["order"])] = by_order.get(str(r["order"]), 0) + 1
        harness.log(line="root_orders", roots=len(w["roots"]),
                    by_order=by_order, roots_that_landed=len(landed),
                    waves_dispatched_ahead=sum(
                        1 for m in w["modules"] if m["ahead"]),
                    modules_in_window=len(w["modules"]))
    return 100.0 * sum(1 for r in landed if r["order"] in orders) \
        / len(landed)

"""Requests answered per second over the window's whole slices: the answers
of every slice that ended inside the window, over the time from the
window's start to the end of the last of them.

The server hands answers back a slice at a time, so a count over the whole
window would jump by one slice's answers as the last slice ends just before
or just after the window closes: 32 answers in about 14 slices, 7%, in the
oversubscribed cell. The time after the last slice holds work that is not
counted, so it is not counted either."""


def read(rec):
    done = [s for s in rec.window_slices() if s.t >= rec.t0 and s.answered]
    if not done or done[-1].t <= rec.t0:
        return None
    return sum(s.answered for s in done) / (done[-1].t - rec.t0)

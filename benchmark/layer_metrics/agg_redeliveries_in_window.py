"""Messages the m3msg producers sent again in the window
(`msg.producer.redeliveries`): 0 while acknowledgements come inside a
message's first backoff (0.2 s)."""


def read(m):
    key = "msg.producer.redeliveries"
    return m.moved(key) if key in m.counters1 else None

"""The decision grammar, written out again as a plain walk over the served
tokens. It is the scheduler's, not a model's: the comparison that decides
`correct` uses it with the reference of every architecture.
"""

from __future__ import annotations


class Grammar:
    """The decision grammar as a walk: which tokens may come next after the
    served tokens so far. Written from its description (a JSON object
    {"selected_node": "<a ready node's name>", "confidence": 0.d | 0.dd |
    1.0, "reasoning": "<printable text without quote or backslash, at most
    max_reason tokens>"} then end-of-sequence), token paths by the
    tokenizer's own encoding of each piece."""

    def __init__(self, encode, eos_id: int, node_names, max_reason: int) -> None:
        self.quote = encode('"')[0]
        self.names = [tuple(encode(n)) for n in node_names]
        self.head = encode('{"selected_node": "')
        self.mid = encode(', "confidence": ')
        self.digits = [encode(str(d))[0] for d in range(10)]
        self.dot, self.comma = encode(".")[0], encode(",")[0]
        self.reason_head = encode(' "reasoning": "')
        self.printable = sorted({encode(chr(c))[0] for c in range(32, 127) if chr(c) not in '"\\'})
        self.tail = encode("}") + [eos_id]
        self.max_reason = max_reason

    def walk(self, served):
        """For each served token: the sorted list of tokens the grammar
        allowed at its place, or None from the first token it did not."""
        out, i, n = [], 0, len(served)

        def forced(seq):
            nonlocal i
            for t in seq:
                if i < n:
                    out.append([t])
                    i += 1

        def step(allowed):
            nonlocal i
            if i < n:
                out.append(sorted(set(allowed)))
                i += 1
                return served[i - 1]
            return None

        forced(self.head)
        path = ()
        while i < n:
            nxt = {nm[len(path)] for nm in self.names if nm[:len(path)] == path and len(nm) > len(path)}
            if path in self.names:
                nxt.add(self.quote)
            t = step(nxt)
            if t == self.quote and path in self.names or t not in nxt:
                break
            path += (t,)
        forced(self.mid)
        d0, d1 = self.digits[0], self.digits[1]
        t = step([d0, d1])
        if t == d1:
            forced([self.dot, d0, self.comma])
        elif t == d0:
            forced([self.dot])
            step(self.digits)
            t = step(self.digits + [self.comma])
            if t is not None and t != self.comma:
                forced([self.comma])
        forced(self.reason_head)
        used = 0
        while i < n:
            t = step(self.printable + [self.quote] if used < self.max_reason else [self.quote])
            if t == self.quote or t not in self.printable:
                break
            used += 1
        forced(self.tail)
        while len(out) < n:
            out.append([])  # tokens past the grammar's end: nothing was allowed
        return out

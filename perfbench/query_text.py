"""Query text from key ranks: the bijection of the configuration
`keys-1e7-strings`.

The AOL log cannot be fetched here, so its rows are `keys-1e7`'s synthetic
ones (generators/querylog_columns.py): integer key ranks, rank 0 the most
asked. This module turns each rank into a query string and each string
back into its rank, so that the program is handed text while the law
(laws/bounded_laplace_geometric.py) keeps seeing integer ranks.

    text = QueryText(config["text"], keys)
    strings = text.strings()            # object array of str, one per rank
    ranks = text.ranks(released_keys)   # int64, -1 where no rank gives it

A query is one to three words of a seeded lexicon of lowercase ASCII
words, by the rank's tier: the first `tiers[0]` ranks (the head of the
Zipf law) are one word, the next `tiers[1]` ** 2 two words, the rest
three, each word a digit of the rank's offset in its tier (the lexicon's
first `tiers[k]` words are the digits of tier k + 1; the head's words go
shortest first, so rank 0 is the shortest query there is). A share
`long_share` of the ranks past the head draw a target length in
`long_lengths` by a hash of the rank and are padded to exactly it with
filler words, a lexicon of its own that no query word is in: the log's
long queries, up to 100 characters. Distinct ranks give distinct strings
(the words before the first filler word are the rank's digits), and
`ranks` parses a string's words back into the rank it would have to be
and keeps it only where that rank's string IS the string; anything else
parses to -1, outside [0, keys). The lexicons depend on the text block's
`seed` alone, never on a run's --seed: a rank names one query in every
run. Numpy only; imports nothing of the program.
"""

import numpy as np

_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
_SPLITMIX = (np.uint64(0x9E3779B97F4A7C15), np.uint64(0xBF58476D1CE4E5B9),
             np.uint64(0x94D049BB133111EB))
_FILLER_MIN, _FILLER_MAX = 2, 8  # a pad word's letters (see _pad)


def _mix(x):
    """splitmix64 of uint64 values: the per-rank hash of the pad law."""
    x = (x + _SPLITMIX[0]).astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * _SPLITMIX[1]
    x = (x ^ (x >> np.uint64(27))) * _SPLITMIX[2]
    return x ^ (x >> np.uint64(31))


def _words(rng, lengths, taken):
    """Distinct random lowercase words of the given lengths, none in
    `taken` (which grows by them), in the order drawn."""
    out = []
    for length in lengths:
        while True:
            word = _LETTERS[rng.integers(0, 26, int(length))].tobytes()
            word = word.decode("ascii")
            if word not in taken:
                taken.add(word)
                out.append(word)
                break
    return out


class QueryText:
    """The bijection between ranks [0, keys) and query strings that the
    configuration's `text` block states."""

    def __init__(self, spec, keys):
        self.keys = int(keys)
        self.tiers = [int(t) for t in spec["tiers"]]
        self.seed = int(spec["seed"])
        self.long_share = float(spec["long_share"])
        self.long_lo, self.long_hi = (int(v) for v in spec["long_lengths"])
        rng = np.random.default_rng(self.seed)
        law = spec["word_lengths"]
        sizes = np.array([int(k) for k in law])
        shares = np.array([float(v) for v in law.values()])
        taken = set()
        self.words = _words(rng, rng.choice(sizes, int(spec["core_words"]),
                                            p=shares / shares.sum()), taken)
        per_length = int(spec["filler_words_per_length"])
        self.filler = {n: _words(rng, [n] * per_length, taken)
                       for n in range(_FILLER_MIN, _FILLER_MAX + 1)}
        self.head = np.argsort([len(w) for w in self.words[:self.tiers[0]]],
                               kind="stable")
        self.head_of = np.argsort(self.head)
        self.index = {w: i for i, w in enumerate(self.words)}
        # Tier k (k words) holds tiers[k - 1] ** k ranks from start[k - 1];
        # the last tier takes whatever ranks are left.
        self.start = [0]
        for k, size in enumerate(self.tiers[:-1], 1):
            self.start.append(self.start[-1] + size ** k)
        if len(self.words) < max(self.tiers):
            raise ValueError("a tier has more digits than the lexicon words")
        last = len(self.tiers)
        if self.keys > self.start[-1] + self.tiers[-1] ** last:
            raise ValueError(f"{self.keys} ranks exceed the tiers' "
                             f"{self.start[-1] + self.tiers[-1] ** last}")

    # ---- rank -> string ----------------------------------------------------

    def _digits(self, ranks):
        """Each rank's word indices [n, tiers], -1 past its last word."""
        ranks = np.asarray(ranks, dtype=np.int64)
        n_words = np.searchsorted(self.start, ranks, side="right")
        digits = np.full((len(ranks), len(self.tiers)), -1, np.int64)
        for k, size in enumerate(self.tiers, 1):
            at = n_words == k
            offset = ranks[at] - self.start[k - 1]
            for j in range(k):
                digits[at, j] = offset % size
                offset //= size
        head = n_words == 1
        digits[head, 0] = self.head[digits[head, 0]]
        return digits

    def _long_target(self, ranks):
        """Each rank's padded length, or 0 where it is not padded: a share
        `long_share` of the ranks past the head, by a hash of the rank."""
        h = _mix(np.asarray(ranks, dtype=np.uint64) ^ np.uint64(self.seed))
        draw = (h >> np.uint64(11)).astype(np.float64) * 2.0**-53
        span = self.long_hi - self.long_lo + 1
        target = self.long_lo + (h % np.uint64(span)).astype(np.int64)
        padded = (draw < self.long_share) & (np.asarray(ranks)
                                             >= self.tiers[0])
        return np.where(padded, target, 0), h

    def _pad(self, room, picks):
        """Filler words that fill `room` characters exactly, a space before
        each: ceil(room / 9) words of 2 to 8 letters, word i the
        `picks[i]`-th of its length."""
        count = -(-room // (_FILLER_MAX + 1))
        letters = room - count
        out = []
        for i in range(count):
            choices = self.filler[letters // count + (i < letters % count)]
            out.append(" " + choices[int(picks[i]) % len(choices)])
        return "".join(out)

    def _forward(self, ranks):
        ranks = np.asarray(ranks, dtype=np.int64)
        digits = self._digits(ranks)
        first = np.array(self.words, dtype=object)
        later = np.array([" " + w for w in self.words] + [""], dtype=object)
        text = first[digits[:, 0]]
        for j in range(1, len(self.tiers)):
            text = text + later[digits[:, j]]  # -1 is the empty word
        target, h = self._long_target(ranks)
        padded = np.nonzero(target)[0]
        most = -(-self.long_hi // (_FILLER_MAX + 1))
        picks = _mix(h[padded, None] + np.arange(most, dtype=np.uint64))
        for i, row in zip(padded, picks):
            room = int(target[i]) - len(text[i])
            if room >= _FILLER_MIN + 1:
                text[i] = text[i] + self._pad(room, row)
        return text

    def strings(self):
        """Every rank's query, as an object array of `str`."""
        return self._forward(np.arange(self.keys, dtype=np.int64))

    # ---- string -> rank ----------------------------------------------------

    def ranks(self, strings):
        """The rank each string is the query of; -1 for a string that no
        rank in [0, keys) gives."""
        strings = list(strings)
        guess = np.full(len(strings), -1, np.int64)
        for i, s in enumerate(strings):
            words = s.split(" ") if isinstance(s, str) else []
            digits = []
            for w in words:
                if w not in self.index:
                    break
                digits.append(self.index[w])
            k = len(digits)
            if not 1 <= k <= len(self.tiers):
                continue
            size = self.tiers[k - 1]
            if max(digits) >= size:
                continue
            if k == 1:
                digits = [int(self.head_of[digits[0]])]
            offset = 0
            for d in reversed(digits):
                offset = offset * size + d
            rank = self.start[k - 1] + offset
            if rank < self.keys:
                guess[i] = rank
        found = np.nonzero(guess >= 0)[0]
        if len(found):
            again = self._forward(guess[found])
            wrong = [j for j, i in enumerate(found) if again[j] != strings[i]]
            guess[found[wrong]] = -1
        return guess


def moments(strings):
    """The length moments a configuration states of its queries: mean
    words, mean bytes (UTF-8; the text is ASCII), the longest, and the
    share of queries longer than 50 bytes."""
    lengths = np.fromiter((len(s) for s in strings), np.int64,
                          count=len(strings))
    spaces = np.fromiter((s.count(" ") for s in strings), np.int64,
                         count=len(strings))
    return {"mean_words": round(float((spaces + 1).mean()), 4),
            "mean_bytes": round(float(lengths.mean()), 4),
            "longest_bytes": int(lengths.max()),
            "share_over_50_bytes": round(float((lengths > 50).mean()), 6)}

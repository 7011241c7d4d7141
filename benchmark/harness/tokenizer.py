"""A total tokenizer for seeded weights: every id is one fixed-width chunk.

The program's ByteTokenizer decodes ids >= 256 to nothing, so a client would
see under 1 % of a 32,000-row vocabulary's tokens, and its eos id would end
a sampled request early. Here every id decodes to `width` hex characters and
there is no eos, so a request of `max_tokens` returns exactly that many
chunks and the client reads each served id back from its chunk. The width
follows the vocabulary: 4 digits up to 65,536 rows, 5 above; the load
generator and the check take it from the tokenizer in use.
"""

from __future__ import annotations

import zlib
from typing import List, Optional, Sequence

WIDTHS = (4, 5)  # characters per token: the first that holds every id


class FixedWidthTokenizer:
    bos_id: Optional[int] = None
    eos_id: Optional[int] = None

    def __init__(self, vocab_size: int) -> None:
        fits = [w for w in WIDTHS if 0 < vocab_size <= 16 ** w]
        if not fits:
            raise ValueError(f"vocab {vocab_size} does not fit {WIDTHS[-1]} hex digits")
        self._vocab = vocab_size
        self.width = fits[0]

    @property
    def vocab_size(self) -> int:
        return self._vocab

    def piece_id(self, piece: str) -> int:
        """The id of one `width`-character piece: its own hex value where that
        is an id, else a hash of it (template text, padding)."""
        if len(piece) == self.width:
            try:
                v = int(piece, 16)
            except ValueError:
                v = -1
            if 0 <= v < self._vocab and piece == f"{v:0{self.width}x}":
                return v
        return zlib.crc32(piece.encode("utf-8")) % self._vocab

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        return [
            self.piece_id(text[i:i + self.width])
            for i in range(0, len(text), self.width)
        ]

    def decode(self, ids: Sequence[int]) -> str:
        return "".join(f"{int(i):0{self.width}x}" for i in ids)

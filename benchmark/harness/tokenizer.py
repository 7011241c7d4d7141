"""A total tokenizer for seeded weights: every id is one fixed-width chunk.

The program's ByteTokenizer decodes ids >= 256 to nothing, so a client would
see under 1 % of a 32,000-row vocabulary's tokens, and its eos id would end
a sampled request early. Here every id decodes to WIDTH hex characters and
there is no eos, so a request of `max_tokens` returns exactly that many
chunks and the client reads each served id back from its chunk.
"""

from __future__ import annotations

import zlib
from typing import List, Optional, Sequence

WIDTH = 4  # characters per token; 16**4 ids at most


class FixedWidthTokenizer:
    bos_id: Optional[int] = None
    eos_id: Optional[int] = None

    def __init__(self, vocab_size: int) -> None:
        if not 0 < vocab_size <= 16 ** WIDTH:
            raise ValueError(f"vocab {vocab_size} does not fit {WIDTH} hex digits")
        self._vocab = vocab_size

    @property
    def vocab_size(self) -> int:
        return self._vocab

    def piece_id(self, piece: str) -> int:
        """The id of one WIDTH-character piece: its own hex value where that
        is an id, else a hash of it (template text, padding)."""
        if len(piece) == WIDTH:
            try:
                v = int(piece, 16)
            except ValueError:
                v = -1
            if 0 <= v < self._vocab and piece == f"{v:0{WIDTH}x}":
                return v
        return zlib.crc32(piece.encode("utf-8")) % self._vocab

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        return [
            self.piece_id(text[i:i + WIDTH]) for i in range(0, len(text), WIDTH)
        ]

    def decode(self, ids: Sequence[int]) -> str:
        return "".join(f"{int(i):0{WIDTH}x}" for i in ids)


def token_count(n_chars: int) -> int:
    return -(-n_chars // WIDTH)

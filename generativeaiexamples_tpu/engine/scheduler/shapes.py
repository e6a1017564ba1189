"""The shapes a dispatch can take, decided in one place.

Every step program of the engine is compiled for a closed set of
shapes, and warm-up compiles exactly that set. :class:`ShapePlan` is
the one owner of the ladders: how many rows a wave holds, how wide a
chunk goes out, which attention window it gathers, which window a
decode block runs with. It is a pure function of ten scalars, built
once in ``LLMEngine.__init__`` and read as ``engine.shapes`` by wave
formation (``SchedulerPolicy.claim_wave``), the chunk walk, the decode
step, the spec fallback, the draft runtime's ladder and the warm-up
(docs/scheduler.md, "The shape of an extend dispatch").
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class ShapePlan:
    prefill_chunk: int
    page_size: int
    max_seq_len: int
    num_slots: int
    prefill_wave_tokens: int
    decode_block: int
    # the family's (models/registry.py): per-slot state beside the pool,
    # a walk over a packed token axis, an extend walk that gathers the
    # window it is given
    fixed_state: bool
    packed: bool
    extend_reads_window: bool
    # whether decode reads through the ragged page kernel
    page_kernel: bool

    def prefill_bucket(self, n: int) -> int:
        """The width of a wave's token array for a longest prompt of
        ``n`` tokens: whole chunks, capacity at most."""
        chunk = self.prefill_chunk
        bucket = ((n + chunk - 1) // chunk) * chunk
        return min(bucket, self.max_seq_len)

    def max_wave_rows(self) -> int:
        """Max rows of a wave under prefill_wave_tokens.

        A fixed-state family gets ONE row a wave: on the chip its chunk
        walk over several LIVE rows now and then never ended (PERF.md
        section 6, PR 29: four runs of ten, cause not found), every
        one-row wave did. No wider program is built or warmed, so no
        setting can reach one."""
        if self.fixed_state:
            return 1
        budget = self.prefill_wave_tokens
        return max(1, min(self.num_slots, budget // max(1, self.prefill_chunk)))

    def wave_sizes(self) -> List[int]:
        """Admission-wave padding ladder + num_slots. Powers of FOUR:
        each rung is a ~40 s compile of the whole unrolled prefill,
        worth up to 3x padding waste."""
        step = 4
        sizes = []
        n = 1
        while n < self.num_slots:
            sizes.append(n)
            n *= step
        sizes.append(self.num_slots)
        return sizes

    def wave_pad(self, n: int) -> int:
        for s in self.wave_sizes():
            if s >= n:
                return s
        return self.num_slots

    def chunk_widths(self) -> List[int]:
        """Width ladder of an extend dispatch, by the row ladder's rule:
        powers of four down from ``prefill_chunk``, whole pages, never
        under one ({128, 512} at a chunk of 512 over pages of 128). No
        finer: every rung multiplies executables."""
        page = self.page_size
        widths = [self.prefill_chunk]
        while widths[-1] % (4 * page) == 0:
            widths.append(widths[-1] // 4)
        return widths[::-1]

    def packed_rungs(self) -> List[int]:
        """The ONE ladder of a packed dispatch: the token counts ``T``
        its axis is padded to. Whole pages at 1 and 1.5 times the powers
        of two, from one page to the most a wave's chunk can hold (the
        row cap x ``prefill_chunk``): {128, 256, 384, 512, 768, 1024,
        1536, 2048} at a chunk of 512 over pages of 128 under
        ``prefill_wave_tokens`` 2048, so under a third of any dispatch
        is padding, and the count grows with the logarithm of the wave,
        not with rows x widths x windows."""
        page = self.page_size
        top = self.max_wave_rows() * self.prefill_chunk
        rungs = {top}
        n = page
        while n < top:
            rungs.add(n)
            if (3 * n // 2) % page == 0 and 3 * n // 2 < top:
                rungs.add(3 * n // 2)
            n *= 2
        return sorted(rungs)

    def packed_windows(self) -> List[int]:
        """The gather windows a packed program holds, ascending: chunk
        ``k``'s rung of ``extend_window`` for every ``k``. The dispatch
        names one by its index (an operand), so they multiply no
        executables."""
        C = self.prefill_chunk
        return sorted({
            self.extend_window(k, C)
            for k in range((self.max_seq_len + C - 1) // C)
        })

    def chunk_rung(
        self, valid: Sequence[int], n_real: int
    ) -> Optional[Tuple[List[int], int, int]]:
        """(live rows, rows dispatched, width) of one chunk of a wave,
        from what the chunk holds: the rows with tokens in THIS chunk
        (the wave's padding rows, past ``n_real``, are never live). A
        packed family: ONE axis at the least token rung that holds the
        live tokens. Any other: the live rows padded up the wave ladder
        under the row cap, at the narrowest width rung that holds the
        longest of them. None where no row is live: such a chunk is not
        dispatched."""
        live = [i for i in range(n_real) if valid[i] > 0]
        if not live:
            return None
        if self.packed:
            need = sum(int(valid[i]) for i in live)
            return live, 1, next(t for t in self.packed_rungs() if t >= need)
        rows = min(self.wave_pad(len(live)), self.max_wave_rows())
        need = max(int(valid[i]) for i in live)
        width = next(w for w in self.chunk_widths() if w >= need)
        return live, rows, width

    def extend_window(self, k: int, width: int) -> int:
        """The static attention window of chunk ``k`` at ``width``. A
        full chunk gathers the power-of-two window that covers it. A
        narrow one has ONE rung, capacity: under the page kernel the
        walk follows each row's live pages whatever the window says, and
        on the gather 128 queries over 4096 keys cost what 512 over 1024
        do, the least a full-width tail pays. A family whose extend walk
        follows each row's own context (not ``extend_reads_window``) has
        that one rung at every width."""
        C = self.prefill_chunk
        if width < C or not self.extend_reads_window:
            return self.max_seq_len
        return self.attention_window(min((k + 1) * C, self.max_seq_len))

    def extend_signatures(self) -> List[Tuple[int, int, int]]:
        """Every (rows, width, window) an extend dispatch can have —
        what ``chunk_rung`` and ``extend_window`` can produce, and
        what warm-up compiles: no other. A packed family: (the carry's
        rows, T, capacity) for every token rung, one program each (the
        chunk's window is an operand of it)."""
        C = self.prefill_chunk
        cap = self.max_wave_rows()
        if self.packed:
            return [(cap, t, self.max_seq_len) for t in self.packed_rungs()]
        chunks = range((self.max_seq_len + C - 1) // C)
        return sorted({
            (n, w, self.extend_window(k, w))
            for n in {min(s, cap) for s in self.wave_sizes()}
            for w in self.chunk_widths()
            for k in chunks
        })

    def attention_window(self, needed: int) -> int:
        """Power-of-two attention window (>=128) covering `needed` rows."""
        w = 128
        while w < needed and w < self.max_seq_len:
            w *= 2
        return min(w, self.max_seq_len)

    def decode_window(self, max_pos: int) -> int:
        """The static attention-window rung a block-decode dispatch at
        frontier ``max_pos`` runs with — ONE rule shared by _decode_once
        and the spec zero-draft fallback so they cannot drift onto
        different executables."""
        # The ragged page kernel tracks per-slot lengths itself (its
        # scalar-prefetched tables): one full-capacity executable
        # instead of a ~40 s recompile at every power-of-two window
        # crossing.
        if self.page_kernel:
            return self.max_seq_len
        return self.attention_window(max_pos + self.decode_block)

    def window_rungs(self) -> List[int]:
        """Every power-of-two attention-window rung up to capacity —
        the executable ladder warmup walks (one XLA program per rung
        per compiled step family)."""
        rungs = []
        w = 128
        while w < self.max_seq_len:
            rungs.append(w)
            w *= 2
        rungs.append(self.max_seq_len)
        return rungs

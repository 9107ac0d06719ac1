"""Traffic kind ``pq_train_stream``: ``train_stream`` on a product quantizer.

The configuration's ``m`` splits every d-dimensional point into m
sub-vectors of d/m coordinates, and the codebook is m sub-codebooks of
``kappa`` codes each, (m, kappa, d/m): the ``kappa`` points that
``train_stream`` samples as its start, split the same way.  Everything
else is ``train_stream``'s: the stream, the chunks, the window and the
numbers the check compares.  The program is the same call,
``MeshExecutor.run_segment``, given the sub-codebooks; the check follows
``reference/pq_train_stream`` in the place of ``reference/train_stream``.

``ctx.variant`` is ``"control"`` (the reference in bfloat16) or
``"half"`` (every other point left out), as in ``train_stream``.
"""

from __future__ import annotations

import numpy as np

import harness

base = harness.load_driver("train_stream")


class Driver(base.Driver):
    def _split_codebook(self) -> None:
        """The sampled (kappa, d) start as m sub-codebooks, in place."""
        import jax

        m = self.cfg["m"]
        kappa, d = self.w0.shape
        w0 = np.asarray(self.w0).reshape(kappa, m, d // m).swapaxes(0, 1)
        self.w0 = jax.device_put(w0, self.w0.sharding)

    def _first_steps(self) -> None:
        self._split_codebook()
        super()._first_steps()

    def _reference_first_steps(self) -> None:
        """The reference, as the variant says, in the program's place."""
        import jax.numpy as jnp

        self._split_codebook()
        control = self.ctx.variant == "control"
        self.first_w, self.first_loss = self._follow(
            dtype=jnp.bfloat16 if control else jnp.float32,
            fault=None if control else self.ctx.variant)
        self.w, self.t, self.k = self.w0, 0, 0

    def _follow(self, **kw) -> tuple[list, list[float]]:
        import jax

        from reference import pq_train_stream as ref

        cfg = self.cfg
        ws, losses = ref.follow(
            self._one_chip(self.w0),
            [self._one_chip(c) for c in self.chunks[:self.n_check]],
            self._one_chip(self.ev), tau=cfg["tau"], eps0=cfg["eps0"],
            decay=cfg["decay"], **kw)
        jax.block_until_ready(ws[-1])
        return ws, losses

    def check(self) -> dict[str, float]:
        """``train_stream``'s gaps, against the product-quantizer
        reference: ``loss_gap``, ``step1_gap`` and ``change_gap``."""
        prog_w = [np.asarray(w, np.float64) for w in self.first_w]
        prog_loss = [float(x) for x in self.first_loss]
        self.first_w = self.first_loss = None
        self.chunks = self.chunks[:self.n_check]
        self.ex = self.w = None
        ref_w, ref_loss = self._follow()
        w0 = np.asarray(self.w0, np.float64)
        ref_w = [np.asarray(w, np.float64) for w in ref_w]

        def change(w):
            return float(np.linalg.norm(w - w0))

        return {
            "loss_gap": max(base.rel_gap(p, r)
                            for p, r in zip(prog_loss, ref_loss)),
            "step1_gap": base.rel_gap(change(prog_w[0]), change(ref_w[0])),
            "change_gap": base.rel_gap(change(prog_w[-1]),
                                       change(ref_w[-1])),
        }

"""Loss blocks (reference: ``python/mxnet/gluon/loss.py``)."""
from __future__ import annotations

from .. import telemetry as _telemetry
from ..base import MXNetError
from .block import HybridBlock


def _apply_weighting(F, loss, weight=None, sample_weight=None):
    if sample_weight is not None:
        loss = F.broadcast_mul(loss, sample_weight)
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(F, pred, label):
    return label.reshape(pred.shape)


class Loss(HybridBlock):
    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.square(label - pred)
        loss = _apply_weighting(F, loss, self._weight / 2, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class L1Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.abs(label - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SoftmaxCrossEntropyLoss(Loss):
    """Reference: ``SoftmaxCrossEntropyLoss`` -- the canonical classifier
    loss (BASELINE configs 1-2).

    Integer labels over logits with the classes on the last axis
    (``sparse_label=True``, ``from_logits=False``, ``axis`` the last one)
    go through ONE op, ``sparse_softmax_ce``: it takes ``pred`` in the
    dtype it arrives in (bf16/fp16 under AMP), computes in float32 inside
    and writes the gradient once, in ``pred``'s dtype.  Dense labels,
    ``from_logits=True`` and any other axis keep ``log_softmax`` +
    ``pick`` / ``sum``.  Which path a call took is counted
    (``loss.softmax_ce_fused`` / ``loss.softmax_ce_fallback``)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        # a Symbol has no rank to ask: there only -1 says "the last axis"
        last = getattr(pred, "ndim", 0) - 1
        fused = self._sparse_label and not self._from_logits \
            and self._axis in (-1, last)
        if _telemetry._ENABLED:
            _telemetry.hooks.loss_softmax_ce(fused)
        if fused:
            loss = F.sparse_softmax_ce(pred, label, keepdims=True)
        else:
            if not self._from_logits:
                pred = F.log_softmax(pred, axis=self._axis)
            if self._sparse_label:
                loss = -F.pick(pred, label, axis=self._axis, keepdims=True)
            else:
                label = _reshape_like(F, pred, label)
                loss = -F.sum(pred * label, axis=self._axis, keepdims=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None,
                       pos_weight=None):
        label = _reshape_like(F, pred, label)
        if not self._from_sigmoid:
            # log-sum-exp stable BCE with logits
            loss = F.relu(pred) - pred * label + \
                F.Activation(-F.abs(pred), act_type="softrelu")
        else:
            eps = 1e-12
            loss = -(F.log(pred + eps) * label +
                     F.log(1.0 - pred + eps) * (1.0 - label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        loss = label * (F.log(label + 1e-12) - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.abs(label - pred)
        loss = F.where(loss > self._rho,
                       loss - 0.5 * self._rho,
                       (0.5 / self._rho) * F.square(loss))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.relu(self._margin - pred * label)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        loss = F.square(F.relu(self._margin - pred * label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, pred, label)
        if self._label_format == "binary":
            label = 2 * label - 1
        loss = F.Activation(-pred * label, act_type="softrelu")
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, anchor, positive, negative, sample_weight=None):
        loss = F.sum(F.square(anchor - positive) - F.square(anchor - negative),
                     axis=self._batch_axis, exclude=True)
        loss = F.relu(loss + self._margin)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, input1, input2, label, sample_weight=None):
        num = F.sum(input1 * input2, axis=-1)
        denom = F.sqrt(F.sum(F.square(input1), axis=-1)) * \
            F.sqrt(F.sum(F.square(input2), axis=-1))
        cos = num / (denom + 1e-12)
        pos = 1.0 - cos
        neg = F.relu(cos - self._margin)
        label = label.reshape(cos.shape)
        loss = F.where(label == 1, pos, neg)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference: ``CTCLoss``;
    ``src/operator/contrib/ctc_loss.cc``).  Uses the standard
    alpha-recursion in log space via lax.scan."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        super().__init__(weight, 0, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def hybrid_forward(self, F, pred, label, pred_lengths=None,
                       label_lengths=None, sample_weight=None):
        import jax.numpy as jnp
        from jax import lax
        from ..ndarray import NDArray

        logits = pred._data if isinstance(pred, NDArray) else pred
        labels = label._data if isinstance(label, NDArray) else label
        if self._layout == "TNC":
            logits = jnp.swapaxes(logits, 0, 1)
        B, T, V = logits.shape
        L = labels.shape[1]
        import jax
        logp = jax.nn.log_softmax(logits, axis=-1)
        blank = 0
        labels_i = labels.astype(jnp.int32)
        # extended label seq: blank, l1, blank, l2, ... blank  (len 2L+1);
        # negative labels are padding (reference convention) and map to
        # blank so they cannot emit
        ext = jnp.full((B, 2 * L + 1), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(jnp.where(labels_i >= 0, labels_i,
                                            blank))
        S = 2 * L + 1
        neg_inf = -1e30
        alpha0 = jnp.full((B, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank])
        alpha0 = alpha0.at[:, 1].set(
            jnp.take_along_axis(logp[:, 0, :], ext[:, 1:2], axis=1)[:, 0])

        same_as_prev2 = jnp.concatenate(
            [jnp.ones((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

        if pred_lengths is not None:
            pl = (pred_lengths._data if isinstance(pred_lengths, NDArray)
                  else pred_lengths).astype(jnp.int32)
        else:
            pl = jnp.full((B,), T, jnp.int32)

        def step(alpha, t):
            a_shift1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
            m = jnp.maximum(jnp.maximum(alpha, a_shift1), a_shift2)
            m_safe = jnp.where(m <= neg_inf / 2, 0.0, m)
            summed = jnp.exp(alpha - m_safe) + jnp.exp(a_shift1 - m_safe) \
                + jnp.exp(a_shift2 - m_safe)
            summed = jnp.where(m <= neg_inf / 2, 0.0, summed)
            newa = m_safe + jnp.log(jnp.maximum(summed, 1e-37))
            newa = jnp.where(m <= neg_inf / 2, neg_inf, newa)
            emit = jnp.take_along_axis(logp[:, t, :], ext, axis=1)
            # Padded timesteps (t >= pred_length) carry alpha unchanged so
            # the final read-off sees each sample's own last valid step.
            active = (t < pl)[:, None]
            return jnp.where(active, newa + emit, alpha), None

        alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
        if label_lengths is not None:
            ll = (label_lengths._data if isinstance(label_lengths, NDArray)
                  else label_lengths).astype(jnp.int32)
        else:
            # infer per-sample length from -1 padding (reference
            # behavior when no explicit label_lengths is given)
            ll = jnp.sum(labels_i >= 0, axis=1).astype(jnp.int32)
        endpos = 2 * ll  # index of final blank
        last1 = jnp.take_along_axis(alpha, endpos[:, None], axis=1)[:, 0]
        last2 = jnp.take_along_axis(alpha, jnp.maximum(endpos - 1, 0)[:, None],
                                    axis=1)[:, 0]
        # an empty label sequence has only the all-blank path: the
        # endpos-1 clamp would read alpha[:,0] twice (double count)
        last2 = jnp.where(ll == 0, neg_inf, last2)
        m = jnp.maximum(last1, last2)
        ll_total = m + jnp.log(jnp.exp(last1 - m) + jnp.exp(last2 - m))
        from ..ndarray import from_jax
        return from_jax(-ll_total)

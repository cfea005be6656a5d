"""A check made of new files only: the harness's answer check, with one
number of its own beside a limit of its own (how many of the sample's
answers never reached the decoder's reference)."""

from harness.checks import AnswerCheck


class Check(AnswerCheck):
    def compare(self, got: dict, params: dict, control: bool):
        numbers, ctrl = super().compare(got, params, control)
        wanted = min(self.traffic["check_answers"], len(got["sample"]))
        numbers["answers_not_compared"] = wanted - len(got["answers"])
        return numbers, ctrl

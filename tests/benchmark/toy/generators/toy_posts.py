"""A generator made of new files only: the harness's closed loop, with a
request body of its own making (the mix's ``body`` plus what the mix names
under ``more_body``)."""

from harness.generators import ClosedLoopPosts


class Generator(ClosedLoopPosts):
    def payload(self, query: str) -> dict:
        return {**super().payload(query), **self.traffic["more_body"]}
